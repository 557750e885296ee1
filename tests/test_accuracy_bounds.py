"""fp64-reference error-bound oracle (HPL-MxP / SGEMM-cube style).

``repro.core.accuracy`` derives a per-FormatSet forward-error bound from
nothing but the registered dtypes; these tests assert that all five
single-device dispatch paths *and* distributed SUMMA stay within it across
sizes and D/S/Q ratios (property-style loops via tests/_hypothesis_compat,
since hypothesis is unavailable), and that the oracle actually rejects a
mis-dispatched result.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import MPMatrix, format_set, schedule
from repro.core.accuracy import (DEFAULT_SAFETY, check_against_fp64,
                                 class_error_bounds, unit_roundoff)
from repro.core.formats import DEFAULT_FORMATS
from repro.core.precision import Policy, make_map
from repro.tune import GemmPlan
from repro.tune import dispatch as TD

T = 8


@pytest.fixture(autouse=True)
def _hermetic_tune(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "plans.json"))
    TD.clear_registry()
    yield
    TD.clear_registry()


# ---------------------------------------------------------------------------
# the bound itself
# ---------------------------------------------------------------------------

def test_unit_roundoff_from_registry_dtypes():
    assert unit_roundoff(jnp.float32) == 2.0 ** -24
    assert unit_roundoff(jnp.bfloat16) == 2.0 ** -8
    assert unit_roundoff(jnp.float16) == 2.0 ** -11
    assert unit_roundoff(jnp.float8_e4m3fn) == 2.0 ** -4
    assert unit_roundoff(jnp.float8_e5m2) == 2.0 ** -3


def test_bounds_order_follows_storage_precision():
    fset = DEFAULT_FORMATS
    pa = np.full((4, 4), fset.high, np.int8)
    pb = np.full((4, 4), fset.high, np.int8)
    pc = np.array([[0, 1], [2, 2]], np.int8)
    b = class_error_bounds(pa, pb, pc, k=32, fset=fset)
    assert b[fset.high] < b[fset.low] < b[fset.low8]


def test_bounds_scale_with_k_and_operand_storage():
    fset = DEFAULT_FORMATS
    hi = np.full((4, 4), fset.high, np.int8)
    lo8 = np.full((4, 4), fset.low8, np.int8)
    pc = np.full((4, 4), fset.high, np.int8)
    tight = class_error_bounds(hi, hi, pc, k=32, fset=fset)[fset.high]
    loose = class_error_bounds(lo8, hi, pc, k=32, fset=fset)[fset.high]
    assert tight < loose            # fp8-stored A widens the bound
    k_big = class_error_bounds(hi, hi, pc, k=4096, fset=fset)[fset.high]
    assert tight < k_big            # fp32 accumulation term grows with K


def test_oracle_rejects_misdispatch():
    """Negative control: a uniform-HIGH map computed at bf16 must violate
    the fp32-class bound — the oracle catches wrong-precision routing."""
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 64)).astype(np.float32)
    b = rng.standard_normal((64, 64)).astype(np.float32)
    pc = np.full((8, 8), DEFAULT_FORMATS.high, np.int8)
    wrong = (jnp.asarray(a).astype(jnp.bfloat16)
             @ jnp.asarray(b).astype(jnp.bfloat16)).astype(jnp.float32)
    rep = check_against_fp64(np.asarray(wrong), a, b, np.zeros_like(a),
                             pc, pc, pc, T, DEFAULT_FORMATS)
    assert not rep["ok"]


# ---------------------------------------------------------------------------
# all five dispatch paths stay inside the bound
# ---------------------------------------------------------------------------

def _general_problem(size, ratio, ratio8, seed, fset):
    pol = Policy(kind="ratio", ratio_high=ratio, ratio_low8=ratio8,
                 seed=seed)
    key = jax.random.PRNGKey(seed)
    ka, kb = jax.random.split(key)
    a = jax.random.normal(ka, (size, size))
    b = jax.random.normal(kb, (size, size))
    mt = size // T
    pa = make_map((size, size), T, pol, fset=fset)
    pb = make_map((size, size), T, pol, fset=fset)
    pc = make_map((size, size), T, pol, fset=fset)
    A = MPMatrix.from_dense(a, pa, T, fset)
    B = MPMatrix.from_dense(b, pb, T, fset)
    C = MPMatrix.from_dense(jnp.zeros((size, size)), pc, T, fset)
    return a, b, A, B, C, (pa, pb, pc)


def _shares(ratios, ratios8=(0.0, 0.25)):
    """Joint (ratio_high, ratio_low8) draws whose role shares fit in one
    map (a sum above 1 is rejected by the map builder)."""
    return st.sampled_from([(r, r8) for r in ratios for r8 in ratios8
                            if r + r8 <= 1.0])


def _low8_share(fset, ratio):
    """Q share for a drawn D share: 0.25 where the set has a LOW8 role
    and the D share leaves room for it."""
    return min(0.25, 1.0 - ratio) if fset.low8 is not None else 0.0


def _check_path(path, size, ratio, ratio8=0.0, seed=0,
                fset=DEFAULT_FORMATS):
    a, b, A, B, C, (pa, pb, pc) = _general_problem(
        size, ratio, ratio8, seed, fset)
    out = TD.execute_plan(GemmPlan(path=path, bm=T, bn=T, bk=T), A, B, C,
                          alpha=1.0, beta=0.0)
    rep = check_against_fp64(
        np.asarray(out.to_dense()), a, b, np.zeros((size, size)),
        pa, pb, pc, T, fset)
    assert rep["ok"], (path, size, ratio, ratio8, rep["worst_ratio"])


@settings(max_examples=8, deadline=None)
@given(size=st.sampled_from([32, 64]),
       shares=_shares([0.0, 0.25, 0.5, 1.0]), seed=st.integers(0, 3))
def test_ref_path_within_bound(size, shares, seed):
    _check_path("ref", size, *shares, seed)


@settings(max_examples=6, deadline=None)
@given(size=st.sampled_from([32, 64]), shares=_shares([0.0, 0.5, 1.0]))
def test_tile_path_within_bound(size, shares):
    _check_path("tile", size, *shares)


@settings(max_examples=6, deadline=None)
@given(size=st.sampled_from([32, 64]), shares=_shares([0.0, 0.5, 1.0]))
def test_grouped_path_within_bound(size, shares):
    _check_path("grouped", size, *shares)


def _ksplit_problem(size, ratio, seed, fset):
    """K-split applicability: B map constant along N (class-sorted along
    K), uniform-LOW C map."""
    key = jax.random.PRNGKey(seed)
    ka, kb = jax.random.split(key)
    a = jax.random.normal(ka, (size, size))
    b = jax.random.normal(kb, (size, size))
    kt = size // T
    n_hi = int(round(ratio * kt))
    kcls = np.concatenate([np.full(n_hi, fset.high, np.int8),
                           np.full(kt - n_hi, fset.low, np.int8)])
    pa = np.full((kt, kt), fset.low, np.int8)
    pb = np.tile(kcls[:, None], (1, kt)).astype(np.int8)
    pc = np.full((kt, kt), fset.low, np.int8)
    A = MPMatrix.from_dense(a, pa, T, fset)
    B = MPMatrix.from_dense(b, pb, T, fset)
    C = MPMatrix.from_dense(jnp.zeros((size, size)), pc, T, fset)
    return a, b, A, B, C, (pa, pb, pc)


@settings(max_examples=6, deadline=None)
@given(size=st.sampled_from([32, 64]),
       ratio=st.sampled_from([0.0, 0.5, 1.0]), seed=st.integers(0, 3))
def test_ksplit_xla_path_within_bound(size, ratio, seed):
    a, b, A, B, C, maps = _ksplit_problem(size, ratio, seed, DEFAULT_FORMATS)
    out = TD.execute_plan(GemmPlan(path="ksplit_xla", bm=T, bn=T, bk=T),
                          A, B, C, alpha=1.0, beta=0.0)
    rep = check_against_fp64(np.asarray(out.to_dense()), a, b,
                             np.zeros((size, size)), *maps, T,
                             DEFAULT_FORMATS)
    assert rep["ok"], (size, ratio, seed, rep["worst_ratio"])


@settings(max_examples=4, deadline=None)
@given(size=st.sampled_from([32, 64]), ratio=st.sampled_from([0.0, 0.5]))
def test_ksplit_pallas_path_within_bound(size, ratio):
    a, b, A, B, C, maps = _ksplit_problem(size, ratio, 1, DEFAULT_FORMATS)
    out = TD.execute_plan(GemmPlan(path="ksplit_pallas", bm=T, bn=T, bk=T),
                          A, B, C, alpha=1.0, beta=0.0)
    rep = check_against_fp64(np.asarray(out.to_dense()), a, b,
                             np.zeros((size, size)), *maps, T,
                             DEFAULT_FORMATS)
    assert rep["ok"], (size, ratio, rep["worst_ratio"])


# ---------------------------------------------------------------------------
# split-accumulation compound formats (repro.split)
# ---------------------------------------------------------------------------

SPLIT_SETS = [format_set("fp16", "split2_fp16"),
              format_set("fp16", "split3_e5m2"),
              format_set("fp8_e5m2", "fp16", "split2_fp16")]


@settings(max_examples=10, deadline=None)
@given(size=st.sampled_from([32, 64]),
       ratio=st.sampled_from([0.25, 0.5, 1.0]),
       path=st.sampled_from(["ref", "split"]),
       which=st.integers(0, len(SPLIT_SETS) - 1), seed=st.integers(0, 2))
def test_split_paths_within_bound(size, ratio, path, which, seed):
    """split2/split3 compound HIGH classes meet their registry-derived
    (recovered-roundoff) bound on both the oracle and the kernel path."""
    fset = SPLIT_SETS[which]
    _check_path(path, size, ratio, _low8_share(fset, ratio), seed, fset)


@pytest.mark.parametrize("fnames, path, ratio", [
    (("fp16", "fp32"), "tile", 0.0),
    (("fp16", "split2_fp16"), "split", 1.0),
    (("fp16", "split3_e5m2"), "split", 1.0),
])
def test_fp16_family_within_registry_bound(fnames, path, ratio):
    """Uniform maps of the fp16 family — plain fp16 (all-LOW) and the split
    compounds that recover fp32 grade from it (all-HIGH) — meet their
    registry-derived bound."""
    _check_path(path, 64, ratio, 0.0, 0, format_set(*fnames))


def test_split_bound_is_fp32_grade():
    """The split2 bound itself certifies ~fp32 accuracy: orders of
    magnitude below the plain-fp16 class bound at the same K."""
    fset = format_set("fp16", "split2_fp16")
    hi = np.full((4, 4), fset.high, np.int8)
    b = class_error_bounds(hi, hi, hi, k=64, fset=fset)[fset.high]
    lo = np.full((4, 4), fset.low, np.int8)
    b16 = class_error_bounds(lo, lo, lo, k=64, fset=fset)[fset.low]
    assert b < b16 / 50.0


def test_oracle_rejects_split_misdispatch():
    """Negative control: uniform split2-HIGH maps with the product computed
    at plain fp16 must violate the recovered-roundoff bound."""
    fset = format_set("fp16", "split2_fp16")
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 64)).astype(np.float32)
    b = rng.standard_normal((64, 64)).astype(np.float32)
    pc = np.full((8, 8), fset.high, np.int8)
    wrong = (jnp.asarray(a).astype(jnp.float16)
             @ jnp.asarray(b).astype(jnp.float16)).astype(jnp.float32)
    rep = check_against_fp64(np.asarray(wrong), a, b, np.zeros_like(a),
                             pc, pc, pc, T, fset)
    assert not rep["ok"]


# ---------------------------------------------------------------------------
# per-tile-scaled integer formats (repro.quant)
# ---------------------------------------------------------------------------

INT_SETS = [format_set("int8_pt", "fp32"),
            format_set("int4_pt", "bf16", "fp32"),
            format_set("int4_pt", "int8_pt", "fp32")]


@settings(max_examples=10, deadline=None)
@given(size=st.sampled_from([32, 64]),
       ratio=st.sampled_from([0.0, 0.25, 0.5, 1.0]),
       path=st.sampled_from(["ref", "tile", "grouped"]),
       which=st.integers(0, len(INT_SETS) - 1), seed=st.integers(0, 2))
def test_int_paths_within_bound(size, ratio, path, which, seed):
    """int8_pt/int4_pt classes meet the quantization-step bound (which
    replaces mantissa roundoff, scaled to the per-tile absmax envelope)
    on the general dispatch paths."""
    fset = INT_SETS[which]
    _check_path(path, size, ratio, _low8_share(fset, ratio), seed, fset)


@settings(max_examples=4, deadline=None)
@given(size=st.sampled_from([32, 64]), ratio=st.sampled_from([0.0, 0.5]),
       path=st.sampled_from(["ksplit_xla", "ksplit_pallas"]))
def test_int_ksplit_paths_within_bound(size, ratio, path):
    """The production serving layout: structured-K maps with int LOW
    blocks stay inside the bound on both ksplit kernels."""
    fset = format_set("int8_pt", "fp32")
    a, b, A, B, C, maps = _ksplit_problem(size, ratio, 1, fset)
    out = TD.execute_plan(GemmPlan(path=path, bm=T, bn=T, bk=T),
                          A, B, C, alpha=1.0, beta=0.0)
    rep = check_against_fp64(np.asarray(out.to_dense()), a, b,
                             np.zeros((size, size)), *maps, T, fset)
    assert rep["ok"], (path, size, ratio, rep["worst_ratio"])


def test_oracle_rejects_int_misdispatch():
    """Negative control: int8-class maps with A actually stored at int4
    must violate the int8 quantization-step bound.  Random data lets
    rounding errors random-walk inside the worst-case bound, so the
    operand is adversarial: every payload element sits exactly on an
    int4 half-step (3.5 under a per-tile scale of 1), making the int4
    error coherent at the full half step across the contraction."""
    fset = format_set("int8_pt", "fp32")
    i4 = format_set("int4_pt", "fp32").fmt(0)
    a = np.full((64, 64), 3.5, np.float32)
    a[::T, ::T] = 7.0               # per-tile absmax → scale exactly 1.0
    b = np.ones((64, 64), np.float32)
    lo = np.full((8, 8), fset.low, np.int8)
    a4 = np.asarray(i4.roundtrip(jnp.asarray(a), tile=T), np.float64)
    assert np.abs(a4 - a).max() == pytest.approx(0.5)   # half of step 1
    wrong = a4 @ np.asarray(b, np.float64)
    rep = check_against_fp64(wrong, a, b, np.zeros_like(a),
                             lo, lo, lo, T, fset)
    assert not rep["ok"]
    # the same product under the int4 bound (what actually ran) passes
    ok = check_against_fp64(wrong, a, b, np.zeros_like(a), lo, lo, lo, T,
                            format_set("int4_pt", "fp32"))
    assert ok["ok"]


def test_int_bound_tracks_quantization_step():
    """The int class bounds are quantization-step-driven: int4's half step
    (0.5/7) dominates int8's (0.5/127) by more than an order of
    magnitude at the same K."""
    s = format_set("int4_pt", "int8_pt", "fp32")
    lo8 = np.full((4, 4), s.low8, np.int8)    # int4
    lo = np.full((4, 4), s.low, np.int8)      # int8
    b4 = class_error_bounds(lo8, lo8, lo8, k=64, fset=s)[s.low8]
    b8 = class_error_bounds(lo, lo, lo, k=64, fset=s)[s.low]
    assert b4 > 10.0 * b8


# ---------------------------------------------------------------------------
# distributed SUMMA stays inside the same bound
# ---------------------------------------------------------------------------

def _summa_within_bound(P, Q, fset, ratio=0.5, ratio8=0.0, seed=0):
    from repro.core.summa import summa_mp_gemm
    size = 64
    pol = Policy(kind="ratio", ratio_high=ratio, ratio_low8=ratio8,
                 seed=seed)
    mt = size // T
    pa = schedule.sorted_balanced_map(mt, mt, pol, axis=0, groups=P,
                                      fset=fset)
    pb = schedule.sorted_balanced_map(mt, mt, pol, axis=1, groups=Q,
                                      fset=fset)
    pc = schedule.balanced_ratio_map(mt, mt, pol, P, Q, fset=fset)
    key = jax.random.PRNGKey(seed)
    ka, kb = jax.random.split(key)
    a = jax.random.normal(ka, (size, size))
    b = jax.random.normal(kb, (size, size))
    A = MPMatrix.from_dense(a, pa, T, fset)
    B = MPMatrix.from_dense(b, pb, T, fset)
    C = MPMatrix.from_dense(jnp.zeros((size, size)), pc, T, fset)
    mesh = jax.make_mesh((P, Q), ("row", "col"))
    out = summa_mp_gemm(A, B, C, mesh=mesh)
    rep = check_against_fp64(np.asarray(out.to_dense()), a, b,
                             np.zeros((size, size)), pa, pb, pc, T, fset)
    assert rep["ok"], (P, Q, fset.key(), rep["worst_ratio"])


@settings(max_examples=6, deadline=None)
@given(shares=_shares([0.0, 0.5, 1.0]), seed=st.integers(0, 2))
def test_summa_1x1_within_bound(shares, seed):
    """SUMMA semantics are mesh-size independent; a 1×1 grid runs the full
    slab/scan machinery on a single device."""
    _summa_within_bound(1, 1, DEFAULT_FORMATS, *shares, seed)


@pytest.mark.parametrize("fs", ["fp8_e4m3+bf16+fp32", "fp8_e5m2+fp16+fp32",
                                "fp16+fp32"])
def test_summa_multi_device_within_bound(host_grid_devices, fs):
    fset = format_set(*fs.split("+"))
    ratio8 = 0.25 if fset.low8 is not None else 0.0
    _summa_within_bound(2, 2, fset, 0.5, ratio8)


def test_safety_factor_is_load_bearing():
    """The default bound is conservative but not vacuous: with safety
    shrunk 100×, at least one real path/ratio violates it."""
    rng_violated = False
    fset = DEFAULT_FORMATS
    for seed in range(3):
        a, b, A, B, C, (pa, pb, pc) = _general_problem(
            64, 0.0, 0.0, seed, fset)
        out = TD.execute_plan(GemmPlan(path="ref", bm=T, bn=T, bk=T),
                              A, B, C)
        rep = check_against_fp64(
            np.asarray(out.to_dense()), a, b, np.zeros((64, 64)),
            pa, pb, pc, T, fset, safety=DEFAULT_SAFETY / 100.0)
        rng_violated = rng_violated or not rep["ok"]
    assert rng_violated
