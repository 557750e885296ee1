"""Tile-centric mixed-precision GEMM semantics (Algorithm 1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import MPMatrix, make_map, mp_gemm_ref, mp_gemm_tilewise_ref
from repro.core.formats import DEFAULT_FORMATS, format_set
from repro.core.mp_gemm import REF_TRACES_METRIC
from repro.core.precision import PAPER_RATIOS, Policy


def _operands(M=48, K=64, N=32, t=16, seeds=(0, 1, 2), ratios=(.5, .3, .6)):
    a = jax.random.normal(jax.random.PRNGKey(seeds[0]), (M, K))
    b = jax.random.normal(jax.random.PRNGKey(seeds[1]), (K, N))
    c = jax.random.normal(jax.random.PRNGKey(seeds[2]), (M, N))
    pa = make_map((M, K), t, Policy(kind="ratio", ratio_high=ratios[0],
                                    seed=seeds[0]))
    pb = make_map((K, N), t, Policy(kind="ratio", ratio_high=ratios[1],
                                    seed=seeds[1]))
    pc = make_map((M, N), t, Policy(kind="ratio", ratio_high=ratios[2],
                                    seed=seeds[2]))
    return (MPMatrix.from_dense(a, pa, t), MPMatrix.from_dense(b, pb, t),
            MPMatrix.from_dense(c, pc, t))


@pytest.mark.parametrize("alpha,beta", [(1.0, 0.0), (1.5, 0.25), (-1.0, 1.0)])
def test_ref_matches_tilewise_oracle(alpha, beta):
    A, B, C = _operands()
    out = mp_gemm_ref(A, B, C, alpha=alpha, beta=beta)
    oracle = mp_gemm_tilewise_ref(A, B, C, alpha=alpha, beta=beta)
    np.testing.assert_allclose(np.asarray(out.to_dense()),
                               np.asarray(oracle), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", list(PAPER_RATIOS))
def test_paper_ratio_configs(name):
    t = 16
    M = K = N = 48
    a = jax.random.normal(jax.random.PRNGKey(0), (M, K))
    b = jax.random.normal(jax.random.PRNGKey(1), (K, N))
    c = jnp.zeros((M, N))
    pol = PAPER_RATIOS[name]
    pa = make_map((M, K), t, pol)
    A = MPMatrix.from_dense(a, pa, t)
    B = MPMatrix.from_dense(b, make_map((K, N), t, pol), t)
    C = MPMatrix.from_dense(c, make_map((M, N), t, pol), t)
    out = mp_gemm_ref(A, B, C)
    # 100D:0S must be exactly the fp32 product
    if name == "100D:0S":
        np.testing.assert_allclose(
            np.asarray(out.to_dense()), np.asarray(a @ b),
            rtol=1e-5, atol=1e-5)
    else:  # mixed: within bf16 error of the fp32 product
        np.testing.assert_allclose(
            np.asarray(out.to_dense()), np.asarray(a @ b),
            rtol=0.15, atol=0.5)


def test_output_stored_in_c_precision():
    A, B, C = _operands(ratios=(1.0, 1.0, 0.5))
    out = mp_gemm_ref(A, B, C)
    # LOW C tiles must round-trip bf16 exactly
    lo = np.asarray(out.lo.astype(jnp.float32))
    hi = np.asarray(out.hi)
    assert (np.asarray(out.cls.arr) == 1).any()
    # disjoint support
    assert not ((np.abs(lo) > 0) & (np.abs(hi) > 0)).any()


def test_accuracy_monotone_in_high_ratio():
    """More HIGH tiles → closer to the fp64 reference (the paper's
    accuracy/performance dial)."""
    M = K = N = 64
    t = 16
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    errs = []
    for ratio in (0.0, 0.5, 1.0):
        pol = Policy(kind="ratio", ratio_high=ratio, seed=1)
        A = MPMatrix.from_dense(a, make_map((M, K), t, pol), t)
        B = MPMatrix.from_dense(b, make_map((K, N), t, pol), t)
        C = MPMatrix.from_dense(jnp.zeros((M, N)),
                                make_map((M, N), t, pol), t)
        out = np.asarray(mp_gemm_ref(A, B, C).to_dense(), np.float64)
        errs.append(np.abs(out - exact).mean())   # mean: max saturates at
    assert errs[2] < errs[1] < errs[0]             # the bf16 output rounding


def _class_operands(fset, M, K, N, t, seed):
    """A, B, C in ``fset`` with every class of the set drawn per tile."""
    rng = np.random.default_rng(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    mats = []
    for key, (r, c) in zip(keys, ((M, K), (K, N), (M, N))):
        cls = rng.integers(0, len(fset), size=(-(-r // t), -(-c // t)))
        mats.append(MPMatrix.from_dense(jax.random.normal(key, (r, c)),
                                        cls.astype(np.int8), t, fset))
    return mats


@pytest.mark.parametrize("alpha,beta", [(1.0, 0.0), (0.5, 2.0)])
@pytest.mark.parametrize("names", [DEFAULT_FORMATS.names,
                                   ("fp16", "split2_fp16"),
                                   ("int8_pt", "bf16")],
                         ids=lambda n: "+".join(n))
def test_compiled_ref_equals_its_eager_body(names, alpha, beta):
    """The one-program ``ref`` GEMM is bit for bit its own body run op by
    op, in every output buffer (padded edges included)."""
    fset = format_set(*names)
    A, B, C = _class_operands(fset, 40, 56, 24, 16, seed=len(names))
    got = mp_gemm_ref(A, B, C, alpha=alpha, beta=beta)
    with jax.disable_jit():
        want = mp_gemm_ref(A, B, C, alpha=alpha, beta=beta)
    assert len(got.bufs) == len(want.bufs) == len(fset)
    for g, w in zip(got.bufs, want.bufs):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g.astype(jnp.float32)),
                                      np.asarray(w.astype(jnp.float32)))


def test_ref_traces_once_per_class_set():
    """The class map is a traced argument: repeated calls and a new map
    with the same classes present reuse one program; a new class set
    traces again."""
    t = 8
    M = K = N = 3 * t              # a shape no other test here compiles
    a = jax.random.normal(jax.random.PRNGKey(5), (M, K))
    A = MPMatrix.from_dense(a, np.array([[0, 1, 2]] * 3, np.int8), t)
    B = MPMatrix.from_dense(a.T, np.array([[2, 1, 0]] * 3, np.int8), t)
    zero = jnp.zeros((M, N))

    def traces():
        return sum(s.value for _, s in
                   obs.metrics_registry().series(REF_TRACES_METRIC))

    def call(cls_map):
        C = MPMatrix.from_dense(zero, np.asarray(cls_map, np.int8), t)
        jax.block_until_ready(mp_gemm_ref(A, B, C).bufs)

    start = traces()
    for _ in range(3):
        call([[1, 2, 1], [2, 1, 2], [1, 1, 1]])
    assert traces() == start + 1
    call([[2, 2, 2], [1, 1, 1], [2, 1, 1]])     # same classes, new map
    assert traces() == start + 1
    call([[0, 2, 2], [1, 1, 1], [2, 1, 1]])     # fp8 tile: a new class set
    assert traces() == start + 2
    assert obs.metrics_registry().value(
        REF_TRACES_METRIC, classes="fp8_e4m3+bf16+fp32") >= 1
