"""repro.tune: cost model, plan validation, cache round-trip, dispatch."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MPMatrix, Policy, make_map, mp_gemm_ref
from repro.core.layout import KSplitWeight, ksplit_matmul
from repro.core.precision import PrecClass
from repro.tune import dispatch as TD
from repro.tune import search as TS
from repro.tune.costmodel import (GemmPlan, GemmProblem, plan_vmem_bytes,
                                  predict_time, validate_plan)
from repro.tune.device import DEVICE_TABLE, detect_device

LOW = int(PrecClass.LOW)
V5E = DEVICE_TABLE["tpu-v5e"]
CPU = DEVICE_TABLE["cpu-interpret"]


@pytest.fixture(autouse=True)
def _isolate_tune_state(tmp_path, monkeypatch):
    """Every test gets an empty registry and its own plan-cache file."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "plans.json"))
    monkeypatch.delenv("REPRO_TUNE_CACHE_ONLY", raising=False)
    monkeypatch.delenv("REPRO_TUNE_DEVICE", raising=False)
    TD.clear_registry()
    TS._default_cache = None
    yield
    TD.clear_registry()
    TS._default_cache = None


def _operands(M, K, N, T, ratio=0.5, *, b_kconst=False, c_uniform=False,
              seed=0):
    pol = Policy(kind="ratio", ratio_high=ratio, seed=seed)
    a = jax.random.normal(jax.random.PRNGKey(seed), (M, K))
    b = jax.random.normal(jax.random.PRNGKey(seed + 1), (K, N))
    pa = make_map((M, K), T, pol)
    pb = (np.repeat(make_map((K, T), T, pol), N // T, axis=1) if b_kconst
          else make_map((K, N), T, pol))
    pc = (np.full((M // T, N // T), LOW, np.int8) if c_uniform
          else make_map((M, N), T, pol))
    return (MPMatrix.from_dense(a, pa, T), MPMatrix.from_dense(b, pb, T),
            MPMatrix.from_dense(jnp.zeros((M, N)), pc, T))


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

def _prob(c_high, tile=256, mnk=2048):
    return GemmProblem(m=mnk, n=mnk, k=mnk, tile=tile,
                       a_high=c_high, b_high=c_high, c_high=c_high,
                       c_classes=(LOW, int(PrecClass.HIGH)))


def test_costmodel_monotonic_in_high_fraction():
    """More HIGH tiles -> more MXU passes -> higher predicted cost."""
    plan = GemmPlan(path="tile", bm=256, bn=256, bk=256)
    fracs = [0.0, 0.25, 0.5, 0.75, 1.0]
    compute = [predict_time(plan, _prob(f), V5E)["compute_s"] for f in fracs]
    total = [predict_time(plan, _prob(f), V5E)["total_s"] for f in fracs]
    assert all(b > a for a, b in zip(compute, compute[1:])), compute
    assert all(b >= a for a, b in zip(total, total[1:])), total


def test_costmodel_high_pass_ratio_matches_device_table():
    plan = GemmPlan(path="tile", bm=256, bn=256, bk=256)
    lo = predict_time(plan, _prob(0.0), V5E)["compute_s"]
    hi = predict_time(plan, _prob(1.0), V5E)["compute_s"]
    assert hi / lo == pytest.approx(
        V5E.class_cost[int(PrecClass.HIGH)], rel=1e-6)


def test_vmem_limit_rejects_plan():
    """tile=1024 -> 22 B/elem working set ~ 23 MB > 90% of v5e's 16 MB."""
    prob = _prob(0.5, tile=1024, mnk=4096)
    plan = GemmPlan(path="tile", bm=1024, bn=1024, bk=1024)
    assert plan_vmem_bytes(plan, prob) > 0.9 * V5E.vmem_bytes
    reasons = validate_plan(plan, prob, V5E)
    assert any("VMEM" in r for r in reasons), reasons
    # and the candidate enumerator never emits it
    cands = TS.candidate_plans(prob, V5E)
    assert all(c.path != "tile" for c in cands)
    assert any(c.path == "ref" for c in cands)  # oracle always available


def test_alignment_rejected_on_real_hw_only():
    prob = _prob(0.5, tile=100, mnk=400)
    plan = GemmPlan(path="tile", bm=100, bn=100, bk=100)
    assert any("alignment" in r for r in validate_plan(plan, prob, V5E))
    assert not any("alignment" in r
                   for r in validate_plan(plan, prob, CPU))


def test_detect_device_forced(monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_DEVICE", "tpu-v6e")
    assert detect_device().kind == "tpu-v6e"
    monkeypatch.setenv("REPRO_TUNE_DEVICE", "no-such-device")
    with pytest.raises(KeyError):
        detect_device()


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_detect_device_maps_known_kinds():
    assert detect_device(_FakeDevice("tpu", "TPU v5 lite")).kind == "tpu-v5e"
    assert detect_device(_FakeDevice("tpu", "TPU v6e")).kind == "tpu-v6e"
    assert detect_device(_FakeDevice("cpu", "cpu")).kind == "cpu-interpret"


@pytest.mark.parametrize("platform,kind", [("tpu", "TPU v99 mega"),
                                           ("gpu", "Radeon Pro W7900")])
def test_detect_device_refuses_unknown_accelerator(platform, kind):
    """No peak rates are assumed for hardware the table does not hold."""
    with pytest.raises(KeyError, match=kind):
        detect_device(_FakeDevice(platform, kind))


def _uniform_problem(fset, code, nt=4, t=128):
    full = np.full((nt, nt), code, np.int8)
    return GemmProblem.from_maps(full, full, full, t, fset=fset)


@pytest.mark.parametrize("path,fs", [
    ("tile", "fp8_e5m2+fp16+fp32"), ("grouped", "fp8_e5m2+fp16+fp32"),
    ("ksplit_pallas", "fp16+fp32"), ("split", "bf16+split2_fp16")])
def test_fp16_sets_stay_off_v5e_pallas_paths(path, fs):
    """float16 in a set's buffers, compute or split slices makes every
    Pallas path invalid on v5e (Mosaic refuses it there), for that reason
    alone; the XLA paths keep serving the set."""
    from repro.core.formats import format_set
    fset = format_set(*fs.split("+"))
    prob = _uniform_problem(
        fset, fset.high if path == "split" else fset.low)
    plan = GemmPlan(path=path)
    bad = validate_plan(plan, prob, V5E)
    assert len(bad) == 1 and "float16" in bad[0], bad
    assert not validate_plan(plan, prob, DEVICE_TABLE["tpu-v6e"])
    xla = ("ref",) if path == "split" else ("ref", "ksplit_xla")
    for p in xla:
        assert not validate_plan(GemmPlan(path=p), prob, V5E)
    assert {p.path for p in TS.candidate_plans(prob, V5E)} \
        <= {"ref", "ksplit_xla"}


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------

def test_plan_cache_roundtrip_and_cache_only_dispatch(monkeypatch):
    A, B, C = _operands(32, 32, 32, 8)
    from repro.tune import autotune, mp_matmul
    plan = autotune(A, B, C, warmup=1, iters=2, max_measure=2)
    path = TS.cache_path()
    assert os.path.exists(path), "autotune must persist the plan cache"

    # fresh cache object reads the same plan back from disk
    fresh = TS.PlanCache(path)
    assert len(fresh) == 1
    key = fresh.keys()[0]
    assert fresh.get(key) == plan
    assert fresh.meta(key)["source"] == "measured"

    # cache-only (CI) mode: dispatch must route via the persisted plan
    # without measuring anything
    monkeypatch.setenv("REPRO_TUNE_CACHE_ONLY", "1")
    TD.clear_registry()
    TS._default_cache = None
    prob = TD.problem_of(A, B, C)
    got, source = TD.resolve_plan(prob)
    assert got == plan and source == "cache"
    out = mp_matmul(A, B, C)
    ref = mp_gemm_ref(A, B, C)
    np.testing.assert_allclose(np.asarray(out.to_dense()),
                               np.asarray(ref.to_dense()),
                               rtol=0, atol=1e-4)


def test_cache_only_mode_never_measures():
    A, B, C = _operands(16, 16, 16, 8)
    os.environ["REPRO_TUNE_CACHE_ONLY"] = "1"
    try:
        prob = TD.problem_of(A, B, C)

        def boom(plan):
            raise RuntimeError("cache-only mode must not execute plans")

        plan, report = TS.autotune_problem(prob, boom)
        assert report["source"] == "model"
        assert not validate_plan(plan, prob, detect_device())
    finally:
        del os.environ["REPRO_TUNE_CACHE_ONLY"]


# ---------------------------------------------------------------------------
# dispatcher numerical equivalence, every routed path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path,kw,tol", [
    ("ref", {}, 0.0),
    ("tile", {}, 1e-4),
    ("grouped", {}, 1e-4),
    ("ksplit_xla", dict(b_kconst=True, c_uniform=True), 2e-2),
    ("ksplit_pallas", dict(b_kconst=True, c_uniform=True), 2e-2),
])
def test_dispatch_matches_reference(path, kw, tol):
    M, K, N, T = 32, 48, 32, 8
    A, B, C = _operands(M, K, N, T, ratio=0.5, **kw)
    from repro.tune import mp_matmul
    plan = GemmPlan(path=path, bm=M if path == "ksplit_pallas" else T,
                    bn=N if path == "ksplit_pallas" else T, bk=T)
    out = mp_matmul(A, B, C, plan=plan)
    ref = mp_gemm_ref(A, B, C)
    scale = float(jnp.abs(ref.to_dense()).max())
    err = float(jnp.abs(out.to_dense() - ref.to_dense()).max())
    assert err <= tol * scale + 1e-12, (path, err, scale)
    assert np.array_equal(out.cls.arr, C.cls.arr)


def test_invalid_plan_is_rejected_with_reasons():
    A, B, C = _operands(32, 32, 32, 8)  # random B map: ksplit inapplicable
    from repro.tune import mp_matmul
    with pytest.raises(ValueError, match="ksplit"):
        mp_matmul(A, B, C, plan=GemmPlan(path="ksplit_xla", bm=8, bn=8,
                                         bk=8))


def test_default_c_is_uniform_low_zero():
    A, B, _ = _operands(16, 24, 16, 8)
    from repro.tune import mp_matmul
    out = mp_matmul(A, B, plan=GemmPlan(path="ref", bm=8, bn=8, bk=8))
    assert (out.cls.arr == LOW).all()
    ref = mp_gemm_ref(A, B, MPMatrix.from_dense(
        jnp.zeros((16, 16)), np.full((2, 2), LOW, np.int8), 8))
    np.testing.assert_allclose(np.asarray(out.to_dense()),
                               np.asarray(ref.to_dense()), atol=1e-6)


# ---------------------------------------------------------------------------
# MPLinear integration
# ---------------------------------------------------------------------------

def test_linear_dispatch_routes_registered_kernel_plan():
    K, N, T, M = 32, 16, 8, 8
    w = jax.random.normal(jax.random.PRNGKey(0), (K, N))
    k_cls = np.array([2, 2, 1, 1], np.int8)  # sorted HIGH,HIGH,LOW,LOW
    ksw = KSplitWeight.from_dense(w, k_cls, T)
    x = jax.random.normal(jax.random.PRNGKey(1), (M, K))
    base = ksplit_matmul(x, ksw)

    # default (no plan): XLA path
    np.testing.assert_array_equal(np.asarray(TD.linear_matmul(x, ksw)),
                                  np.asarray(base))

    # register the Pallas kernel plan for this signature -> routed
    dev = detect_device()
    prob = TD.linear_problem(ksw, M)
    TD.register_plan(TS.plan_key(dev, prob),
                     GemmPlan(path="ksplit_pallas", bm=M, bn=N, bk=T))
    routed = TD.linear_matmul(x, ksw)
    np.testing.assert_allclose(np.asarray(routed), np.asarray(base),
                               rtol=2e-2, atol=1e-4)


def test_tune_linear_params_fills_registry():
    from repro.core.linear import init_mp_linear
    lin = init_mp_linear(jax.random.PRNGKey(0), 64, 32,
                         Policy(kind="ratio", ratio_high=0.5), tile=8)
    plans = TD.tune_linear_params({"lin": lin}, m_hint=16)
    assert len(plans) == 1
    (key, plan), = plans.items()
    assert plan.path in ("ksplit_xla", "ksplit_pallas")
    # the layer itself still evaluates correctly through the dispatcher
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 64))
    y = lin(x)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ksplit_matmul(x, lin.w)),
                               rtol=2e-2, atol=1e-4)


# ---------------------------------------------------------------------------
# plan-cache hygiene (CI tune-cache-hygiene step)
# ---------------------------------------------------------------------------

def test_hygiene_checked_in_cache_is_clean():
    from repro.tune.hygiene import validate_cache
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "results", "tune_cache.json")
    assert validate_cache(path) == []


def test_hygiene_detects_drift(tmp_path):
    import json

    from repro.tune.hygiene import validate_cache
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "results", "tune_cache.json")
    with open(path) as f:
        payload = json.load(f)

    # stale v1 key (ratio segment where the format set belongs)
    bad = dict(payload)
    key = next(iter(payload["plans"]))
    v1_key = "|".join(k for i, k in enumerate(key.split("|")) if i != 4)
    bad["plans"] = {**payload["plans"],
                    v1_key: payload["plans"][key]}
    p = tmp_path / "v1.json"
    p.write_text(json.dumps(bad, indent=1, sort_keys=True))
    assert any("v1" in msg for msg in validate_cache(str(p)))

    # wrong schema
    bad = {**payload, "schema": 1}
    p = tmp_path / "schema.json"
    p.write_text(json.dumps(bad, indent=1, sort_keys=True))
    assert any("schema" in msg for msg in validate_cache(str(p)))

    # non-canonical ordering / formatting
    p = tmp_path / "order.json"
    p.write_text(json.dumps(payload, indent=2, sort_keys=False))
    assert any("canonical" in msg for msg in validate_cache(str(p)))

    # missing format stamps
    bad = {k: v for k, v in payload.items() if k != "formats"}
    p = tmp_path / "stamps.json"
    p.write_text(json.dumps(bad, indent=1, sort_keys=True))
    assert any("stamps" in msg for msg in validate_cache(str(p)))


def test_hygiene_rejects_unregistered_format_keys(tmp_path):
    """A checked-in cache entry naming a format that is not registered in
    this process would be shelved forever by PlanCache — hygiene must
    reject it with a descriptive error."""
    import json

    from repro.tune.hygiene import validate_cache
    key = ("cpu-interpret|mp_gemm|M64N64K64|t16|bf16+fp99_custom"
           "|50D50S|50D50S|50D50S|a1b1k0p1c1")
    payload = {"schema": 2,
               "formats": {"fp99_custom": "fp99_custom:sig"},
               "plans": {key: {"path": "ref", "bm": 16, "bn": 16,
                               "bk": 16}}}
    p = tmp_path / "unreg.json"
    p.write_text(json.dumps(payload, indent=1, sort_keys=True))
    msgs = validate_cache(str(p))
    assert any("not registered" in m and "fp99_custom" in m for m in msgs)
    # split compound formats ARE registered → no such problem
    ok_key = key.replace("bf16+fp99_custom", "fp16+split2_fp16")
    payload["plans"] = {ok_key: payload["plans"][key]}
    payload["formats"] = {"fp16": "x", "split2_fp16": "y"}
    p2 = tmp_path / "split.json"
    p2.write_text(json.dumps(payload, indent=1, sort_keys=True))
    assert not any("not registered" in m for m in validate_cache(str(p2)))


def test_hygiene_writer_emits_canonical_file(tmp_path):
    from repro.tune.costmodel import GemmPlan
    from repro.tune.hygiene import validate_cache

    path = str(tmp_path / "cache.json")
    cache = TS.PlanCache(path)
    A, B, C = _operands(64, 64, 64, 16)
    prob = TD.problem_of(*TD.canonical_operands(A, B, C))
    key = TS.plan_key(TS.detect_device(), prob)
    # insertion order deliberately unsorted: z-device first
    cache.put("z" + key, GemmPlan(path="ref", bm=16, bn=16, bk=16))
    cache.put(key, GemmPlan(path="ref", bm=16, bn=16, bk=16))
    assert validate_cache(path) == []


def test_resolve_plans_for_buckets():
    from repro.core.linear import init_mp_linear
    lin = init_mp_linear(jax.random.PRNGKey(0), 64, 32,
                         Policy(kind="ratio", ratio_high=0.5), tile=8)
    params = {"lin": lin}
    table = TD.resolve_plans_for_buckets(
        {"default": params, "alt": params},
        [("default", 4, 8), ("default", 4, 16), ("alt", 4, 8)])
    # deduped on (tag, batch): two tags x one batch size
    assert set(table) == {("default", 4), ("alt", 4)}
    for plans in table.values():
        assert all(p.path in ("ksplit_xla", "ksplit_pallas")
                   for p in plans.values())
    with pytest.raises(KeyError):
        TD.resolve_plans_for_buckets({"default": params},
                                     [("missing", 4, 8)])
