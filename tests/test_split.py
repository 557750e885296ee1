"""Ozaki-style split-accumulation subsystem (``repro.split``).

Covers the slice algebra (round-trip exactness scale, store idempotence,
deterministic pair order), the fp32-grade recovery claim (split2_fp16
beats plain fp16 by orders of magnitude against fp64), bitwise ref ↔
Pallas-kernel parity, compound-format registry semantics, the ``split``
dispatch path's cost-model rules, and the solver's compute-higher
escalation rung.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MPMatrix
from repro.core.formats import format_set, get_format, split_slices
from repro.split import (SPLIT2_FP16, SPLIT3_E5M2, SplitFormat, recombine,
                         slice_pair_order, split_dot_general,
                         split_format_specs, split_gemm_ref, split_variant)
from repro.tune import dispatch as TD
from repro.tune.costmodel import GemmPlan, GemmProblem, validate_plan
from repro.tune.device import DEVICE_TABLE

T = 16
SPLIT2_SET = format_set("fp16", "split2_fp16")
SPLIT3_SET = format_set("fp16", "split3_e5m2")


@pytest.fixture(autouse=True)
def _hermetic_tune(tmp_path, monkeypatch):
    from repro.tune import search as TS
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "plans.json"))
    monkeypatch.delenv("REPRO_TUNE_CACHE_ONLY", raising=False)
    TD.clear_registry()
    TS._default_cache = None
    yield
    TD.clear_registry()
    TS._default_cache = None


def _problem(size, code, seed=0, fset=SPLIT2_SET):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((size, size)).astype(np.float32)
    b = rng.standard_normal((size, size)).astype(np.float32)
    cls = np.full((size // T, size // T), code, np.int8)
    A = MPMatrix.from_dense(a, cls, T, fset)
    B = MPMatrix.from_dense(b, cls, T, fset)
    C = MPMatrix.from_dense(np.zeros_like(a), cls, T, fset)
    return a, b, A, B, C, cls


# ---------------------------------------------------------------------------
# slice algebra
# ---------------------------------------------------------------------------

def test_registered_compound_formats():
    assert isinstance(get_format("split2_fp16"), SplitFormat)
    assert isinstance(get_format("split3_e5m2"), SplitFormat)
    assert SPLIT2_FP16.recovered_roundoff() == 2.0 ** -22
    assert SPLIT3_E5M2.recovered_roundoff() == 2.0 ** -9
    # the recovered roundoff is what the error bounds must see
    assert SPLIT2_FP16.storage_roundoff() == 2.0 ** -22
    assert SPLIT2_FP16.operational_roundoff() == 2.0 ** -22
    # storage is the fp32 mirror buffer; semantic bytes are the slices
    assert SPLIT2_FP16.buffer_dtype == jnp.float32
    assert SPLIT2_FP16.bytes_per_elem == 4
    assert SPLIT3_E5M2.bytes_per_elem == 3


def test_split_roundtrip_error_scale_and_idempotence():
    """Recombined slices reproduce fp32 values to the recovered roundoff
    at the *tile magnitude* scale (fp16 subnormal underflow makes tiny
    elements relatively worse, but the GEMM bound scales by |A|·|B|), and
    store() is exactly idempotent."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((64, 64)).astype(np.float32))
    for fmt, slack in ((SPLIT2_FP16, 4.0), (SPLIT3_E5M2, 4.0)):
        parts = split_slices(x, fmt.slices, jnp.dtype(fmt.slice_dtype))
        assert len(parts) == fmt.slices
        got = recombine(parts)
        scale = float(jnp.abs(x).max())
        err = float(jnp.abs(got - x).max()) / scale
        assert err <= slack * fmt.recovered_roundoff(), (fmt.name, err)
        once = fmt.roundtrip(x)
        np.testing.assert_array_equal(np.asarray(fmt.roundtrip(once)),
                                      np.asarray(once))


def test_slice_pair_order_is_smallest_terms_first():
    assert slice_pair_order(2) == ((1, 1), (1, 0), (0, 1), (0, 0))
    order3 = slice_pair_order(3)
    assert len(order3) == 9 and order3[-1] == (0, 0)
    sums = [i + j for i, j in order3]
    assert sums == sorted(sums, reverse=True)


def test_split_dot_recovers_fp32_grade():
    """The headline claim: fp16×fp16 slice products accumulated in fp32
    recover ~fp32 accuracy where plain fp16 compute loses ~2^-11."""
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((64, 64)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((64, 64)).astype(np.float32))
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    scale = np.abs(exact).max()
    split = np.asarray(split_dot_general(a, b, SPLIT2_FP16), np.float64)
    plain = np.asarray(
        (a.astype(jnp.float16) @ b.astype(jnp.float16)).astype(jnp.float32),
        np.float64)
    err_split = np.abs(split - exact).max() / scale
    err_plain = np.abs(plain - exact).max() / scale
    assert err_split < 1e-6
    assert err_plain > 100 * err_split


def test_split_dot_is_deterministic():
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((32, 32)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((32, 32)).astype(np.float32))
    one = np.asarray(split_dot_general(a, b, SPLIT2_FP16))
    two = np.asarray(split_dot_general(a, b, SPLIT2_FP16))
    np.testing.assert_array_equal(one, two)


def test_split_variant_swaps_the_high_role():
    fs = split_variant(format_set("fp8_e5m2", "fp16", "fp32"))
    assert fs.names == ("fp8_e5m2", "fp16", "split2_fp16")
    assert fs.high == 2
    with pytest.raises(ValueError, match="not a split compound format"):
        split_variant(SPLIT2_SET, "fp32")


def test_split_format_specs_rows():
    specs = split_format_specs(SPLIT2_SET)
    assert specs[0][3] == 1                     # plain fp16: one pass
    assert specs[1][3] == 2                     # split2: two slices
    assert specs[1][4] == "float16"


# ---------------------------------------------------------------------------
# kernel ↔ reference lowering parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fset", [SPLIT2_SET, SPLIT3_SET],
                         ids=lambda f: f.key())
def test_kernel_matches_ref_lowering_bitwise(fset):
    from repro.kernels import ops
    rng = np.random.default_rng(5)
    size = 2 * T
    a = rng.standard_normal((size, size)).astype(np.float32)
    b = rng.standard_normal((size, size)).astype(np.float32)
    cls = rng.integers(0, 2, size=(2, 2)).astype(np.int8)
    cls[0, 0] = 1                                # ≥1 split C tile
    A = MPMatrix.from_dense(a, cls, T, fset)
    B = MPMatrix.from_dense(b, cls, T, fset)
    C = MPMatrix.from_dense(np.zeros_like(a), cls, T, fset)
    ref = split_gemm_ref(A, B, C)
    ker = ops.split_mp_gemm(A, B, C)
    for code, (rb, kb) in enumerate(zip(ref.bufs, ker.bufs)):
        np.testing.assert_array_equal(np.asarray(rb), np.asarray(kb),
                                      err_msg=f"buffer {code}")


def test_split_gemm_beats_plain_fp16_end_to_end():
    from repro.kernels import ops
    a, b, A, B, C, _cls = _problem(64, SPLIT2_SET.high)
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    out = np.asarray(ops.split_mp_gemm(A, B, C).to_dense(), np.float64)
    rel = np.abs(out - exact).max() / np.abs(exact).max()
    assert rel < 1e-6


# ---------------------------------------------------------------------------
# dispatch + cost model
# ---------------------------------------------------------------------------

def test_candidates_for_split_c_classes():
    from repro.tune import search as TS
    _a, _b, _A, _B, _C, cls = _problem(64, SPLIT2_SET.high)
    prob = GemmProblem.from_maps(cls, cls, cls, T, fset=SPLIT2_SET)
    dev = DEVICE_TABLE["cpu-interpret"]
    paths = {p.path for p in TS.candidate_plans(prob, dev)}
    assert paths == {"ref", "split"}


def test_validate_plan_split_rules():
    dev = DEVICE_TABLE["cpu-interpret"]
    split_c = GemmProblem(m=64, n=64, k=64, tile=T,
                          c_classes=(SPLIT2_SET.high,),
                          formats=SPLIT2_SET.key())
    plain_c = GemmProblem(m=64, n=64, k=64, tile=T,
                          c_classes=(SPLIT2_SET.low,),
                          formats=SPLIT2_SET.key())
    tile_plan = GemmPlan(path="tile", bm=T, bn=T, bk=T)
    split_plan = GemmPlan(path="split", bm=T, bn=T, bk=T)
    assert any("split" in r for r in validate_plan(tile_plan, split_c, dev))
    assert not validate_plan(split_plan, split_c, dev)
    # split path without a split C class is pointless → invalid
    assert any("split path needs" in r
               for r in validate_plan(split_plan, plain_c, dev))
    # ksplit paths compute at slice dtype — split fsets rejected wholesale
    ks = GemmPlan(path="ksplit_xla", bm=T, bn=T, bk=T)
    ks_prob = GemmProblem(m=64, n=64, k=64, tile=T, b_k_constant=True,
                          c_classes=(SPLIT2_SET.low,),
                          formats=SPLIT2_SET.key())
    assert any("split compound" in r for r in validate_plan(ks, ks_prob, dev))


def test_mp_matmul_routes_split_and_counts_dispatch():
    from repro import obs
    _a, _b, A, B, C, _cls = _problem(48, SPLIT2_SET.high)
    plan = GemmPlan(path="split", bm=T, bn=T, bk=T)
    before = obs.metrics_registry().value(
        "dispatch.calls", path="split", op="mp_gemm",
        formats=SPLIT2_SET.key())
    out = TD.mp_matmul(A, B, C, plan=plan)
    after = obs.metrics_registry().value(
        "dispatch.calls", path="split", op="mp_gemm",
        formats=SPLIT2_SET.key())
    assert after == before + 1
    ref = TD.mp_matmul(A, B, C, plan=GemmPlan(path="ref", bm=T, bn=T, bk=T))
    err = float(jnp.abs(out.to_dense() - ref.to_dense()).max())
    scale = float(jnp.abs(ref.to_dense()).max())
    assert err <= 1e-5 * scale


def test_split_pass_costs_price_the_tradeoff():
    """split2 = 4 low passes: cheaper than fp32's 3 bf16 passes on GPU
    (1 fp16 pass), more expensive on the v5e MXU table."""
    v5e, a100 = DEVICE_TABLE["tpu-v5e"], DEVICE_TABLE["gpu-a100"]
    assert v5e.format_cost("split2_fp16") == 4.0
    assert v5e.format_cost("split2_fp16") > v5e.format_cost("fp32")
    assert a100.format_cost("split2_fp16") < a100.format_cost("fp32")


# ---------------------------------------------------------------------------
# solver compute-higher rung
# ---------------------------------------------------------------------------

def test_solver_compute_higher_rung(monkeypatch):
    """``compute_escalation="auto"`` must choose the split variant via the
    cost model, converge, and issue zero mid-solve retunes."""
    monkeypatch.setenv("REPRO_TUNE_CACHE_ONLY", "1")
    from repro.solve import SolveConfig, graded_spd, rhs_for_solution, solve
    a = graded_spd(128, cond=1e4, rho=0.8, seed=0)
    _xt, b = rhs_for_solution(a, nrhs=16, seed=1)
    rep = solve(a, b, SolveConfig(
        tile=T, fset=format_set("fp16", "fp32"),
        compute_escalation="auto", max_sweeps=40))
    assert rep.compute_mode == "split"
    assert rep.split_cost_s < rep.store_cost_s
    assert rep.converged
    assert rep.fresh_resolutions == 0


def test_solver_compute_escalation_validation():
    from repro.solve import SolveConfig, solve
    a = np.eye(32) * 4.0
    b = np.ones((32, 1))
    with pytest.raises(ValueError, match="store | split | auto"):
        solve(a, b, SolveConfig(
            tile=T, fset=format_set("fp16", "fp32"),
            compute_escalation="bogus"))
