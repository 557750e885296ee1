"""Unified mixed-precision GEMM dispatch — ``mp_matmul`` and the plan
registry.

Every execution path the repo grew (reference semantics, the Pallas tile
kernel, the compact grouped kernel, the KSplit XLA dots, the KSplit Pallas
kernel) is registered here behind one entry point; a resolved ``GemmPlan``
(explicit argument > in-memory registry > persisted cache > cost-model best)
picks the path and block shape.  This is the runtime brain the paper
delegates to PaRSEC's hardware-aware scheduler.

The ``linear_matmul`` hook is the same mechanism for ``MPLinear``: the layer
asks the registry for a plan keyed by its (M, K, N, tile, class-ratio)
signature instead of hardcoding the XLA ksplit path, and
``tune_linear_params`` fills that registry once at setup (serve engine /
train step).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.layout import (CompactMPMatrix, KSplitWeight, MPMatrix,
                               ksplit_matmul)
from repro.core.mp_gemm import mp_gemm_ref
from repro.kernels import ops
from repro.tune.costmodel import GemmPlan, GemmProblem, PATHS, validate_plan
from repro.tune.device import DeviceSpec, detect_device
from repro.tune import search as S

#: in-memory plan registry: plan-cache key -> GemmPlan
_REGISTRY: dict[str, GemmPlan] = {}

#: metrics-registry name of the plan-resolution counter, labeled by source
#: ("registry"/"cache"/"model"/"default", prefixed "summa_" for distributed
#: resolutions).  The refinement solver (repro.solve) snapshots these after
#: its ladder prefetch and asserts that no "model"/"default" resolution —
#: i.e. no retune or un-prefetched fallback — happens mid-solve.
RESOLUTION_METRIC = "tune.plan_resolutions"

#: metrics-registry name of the per-dispatch call counter, labeled by
#: execution path / op / format-set tag
DISPATCH_METRIC = "dispatch.calls"


def _count_resolution(source: str, key: str | None = None) -> None:
    obs.metrics_registry().counter(RESOLUTION_METRIC, source=source).inc()
    if key is not None and obs.is_enabled():
        obs.event("plan.resolve", "plan", key=key, source=source)


def resolution_counters() -> dict[str, int]:
    """Deprecated alias — ``{source: count}`` view of the
    ``tune.plan_resolutions`` metric in ``repro.obs.metrics_registry()``
    (the module-global dict this wrapped now lives there)."""
    return {labels["source"]: int(c.value) for labels, c in
            obs.metrics_registry().series(RESOLUTION_METRIC)}


def reset_resolution_counters() -> None:
    """Deprecated alias for resetting ``tune.plan_resolutions`` in the
    metrics registry (explicit, thread-safe reset)."""
    obs.metrics_registry().reset(RESOLUTION_METRIC)


def fresh_resolutions(counters: dict[str, int] | None = None) -> int:
    """Number of resolutions since the last reset that did *fresh* work
    (cost-model ranking or un-prefetched fallback) rather than serving a
    registry/cache hit — the quantity that must be zero mid-solve."""
    c = resolution_counters() if counters is None else counters
    return sum(v for k, v in c.items()
               if k.split("summa_")[-1] in ("model", "default"))


def clear_registry() -> None:
    _REGISTRY.clear()


def register_plan(key: str, plan: GemmPlan) -> None:
    _REGISTRY[key] = plan


def warm_registry(cache: S.PlanCache | None = None) -> int:
    """Load every persisted plan into the in-memory registry (the tune-once
    setup step of serve/train).  Returns the number of plans loaded."""
    cache = cache or S.default_cache()
    n = 0
    for key in cache.keys():
        _REGISTRY[key] = cache.get(key)
        n += 1
    return n


# ---------------------------------------------------------------------------
# Problem construction
# ---------------------------------------------------------------------------

def canonical_operands(a: MPMatrix, b: MPMatrix, c: MPMatrix | None
                       ) -> tuple[MPMatrix, MPMatrix, MPMatrix]:
    """Default C (when omitted) is a zero matrix with a uniform-LOW map —
    the memory-optimal output the paper's 0D endpoint would choose."""
    if not isinstance(a, MPMatrix) or not isinstance(b, MPMatrix):
        raise TypeError("mp_matmul operands must be MPMatrix")
    if a.tile != b.tile:
        raise ValueError(f"tile mismatch {a.tile} vs {b.tile}")
    if a.fset != b.fset or (c is not None and c.fset != a.fset):
        raise ValueError("mp_matmul operands must share a format set")
    if a.cls.arr.shape[1] != b.cls.arr.shape[0]:
        raise ValueError(
            f"inner tile-grid mismatch {a.cls.arr.shape} · {b.cls.arr.shape}")
    if c is not None:
        if c.tile != a.tile:
            raise ValueError(f"C tile {c.tile} != A/B tile {a.tile}")
        if c.cls.arr.shape != (a.cls.arr.shape[0], b.cls.arr.shape[1]):
            raise ValueError(
                f"C tile grid {c.cls.arr.shape} incompatible with "
                f"{a.cls.arr.shape} · {b.cls.arr.shape}")
    if c is None:
        mt = a.cls.arr.shape[0]
        nt = b.cls.arr.shape[1]
        cmap = np.full((mt, nt), a.fset.low, np.int8)
        c = MPMatrix.from_dense(
            jnp.zeros((a.shape[0], b.shape[1]), jnp.float32), cmap, a.tile,
            a.fset)
    return a, b, c


def problem_of(a: MPMatrix, b: MPMatrix, c: MPMatrix, *,
               alpha: float = 1.0, beta: float = 0.0) -> GemmProblem:
    pad_free = (a.shape == a.padded_shape and b.shape == b.padded_shape
                and c.shape == c.padded_shape)
    return GemmProblem.from_maps(
        a.cls.arr, b.cls.arr, c.cls.arr, a.tile,
        alpha=alpha, beta=beta, pad_free=pad_free, fset=a.fset)


# ---------------------------------------------------------------------------
# Path executors
# ---------------------------------------------------------------------------

def _exec_ref(plan, a, b, c, alpha, beta):
    return mp_gemm_ref(a, b, c, alpha=alpha, beta=beta)


def _exec_tile(plan, a, b, c, alpha, beta):
    return ops.mp_gemm(a, b, c, alpha=alpha, beta=beta)


def _exec_split(plan, a, b, c, alpha, beta):
    return ops.split_mp_gemm(a, b, c, alpha=alpha, beta=beta)


def _exec_grouped(plan, a, b, c, alpha, beta):
    t = a.tile
    with obs.span("gemm.prepare", "gemm", path=plan.path):
        ac = CompactMPMatrix.from_dense(a.to_dense(), a.cls.arr, t, a.fset)
        bc = CompactMPMatrix.from_dense(b.to_dense(), b.cls.arr, t, b.fset)
    out = ops.grouped_mp_gemm(ac, bc, c.cls.arr)
    dense = out.to_dense()[: c.shape[0], : c.shape[1]]
    return MPMatrix.from_dense(dense, c.cls.arr, t, c.fset)


def _ksplit_weight(b: MPMatrix) -> KSplitWeight:
    return KSplitWeight.from_dense(b.to_dense(), b.cls.arr[:, 0], b.tile,
                                   b.fset)


def _finish_c(y, c: MPMatrix, alpha, beta):
    out = alpha * y
    if beta != 0.0:
        out = out + beta * c.to_dense()
    return MPMatrix.from_dense(out, c.cls.arr, c.tile, c.fset)


def _exec_ksplit_xla(plan, a, b, c, alpha, beta):
    with obs.span("gemm.prepare", "gemm", path=plan.path):
        w = _ksplit_weight(b)
    y = ksplit_matmul(a.to_dense(), w)
    return _finish_c(y, c, alpha, beta)


def _exec_ksplit_pallas(plan, a, b, c, alpha, beta):
    with obs.span("gemm.prepare", "gemm", path=plan.path):
        w = _ksplit_weight(b)
        x = a.to_dense()
        # the kernel consumes x with class-contiguous K columns (storage
        # order)
        parts = KSplitWeight.k_partition(w.k_cls.arr, w.tile, w.fset)
        xp = jnp.concatenate(
            [x[:, jnp.asarray(idx)] for idx in parts if len(idx)], axis=-1)
    y = ops.ksplit_matmul_kernel(xp, w, bm=plan.bm, bn=plan.bn, bk=plan.bk)
    return _finish_c(y, c, alpha, beta)


_EXECUTORS = {
    "ref": _exec_ref,
    "tile": _exec_tile,
    "grouped": _exec_grouped,
    "ksplit_xla": _exec_ksplit_xla,
    "ksplit_pallas": _exec_ksplit_pallas,
    "split": _exec_split,
}
assert set(_EXECUTORS) == set(PATHS)


def execute_plan(plan: GemmPlan, a: MPMatrix, b: MPMatrix, c: MPMatrix,
                 *, alpha: float = 1.0, beta: float = 0.0) -> MPMatrix:
    return _EXECUTORS[plan.path](plan, a, b, c, alpha, beta)


# ---------------------------------------------------------------------------
# Plan resolution + public entry point
# ---------------------------------------------------------------------------

def _lookup_plan(prob: GemmProblem, dev: DeviceSpec
                 ) -> tuple[GemmPlan, str] | None:
    """Shared registry → persisted-cache lookup.  A stored plan is only
    served if it is still valid for THIS problem (belt-and-braces on top of
    the struct_key: registry entries can be hand-registered, and cache
    files can come from other builds)."""
    key = S.plan_key(dev, prob)
    plan = _REGISTRY.get(key)
    if plan is not None and not validate_plan(plan, prob, dev):
        return plan, "registry"
    plan = S.default_cache().get(key)
    if plan is not None and not validate_plan(plan, prob, dev):
        _REGISTRY[key] = plan
        return plan, "cache"
    return None


def resolve_plan(prob: GemmProblem, dev: DeviceSpec | None = None,
                 paths: Iterable[str] = PATHS) -> tuple[GemmPlan, str]:
    """registry > persisted cache > cost-model best.  Returns (plan, source).
    Never measures — resolution must be cheap enough for trace time."""
    dev = dev or detect_device()
    key = S.plan_key(dev, prob)
    hit = _lookup_plan(prob, dev)
    if hit is not None:
        _count_resolution(hit[1], key)
        return hit
    ranked = S.rank_plans(S.candidate_plans(prob, dev, paths), prob, dev)
    if not ranked:
        raise ValueError(f"no valid plan for {key}")
    plan = ranked[0][0]
    _REGISTRY[key] = plan
    _count_resolution("model", key)
    return plan, "model"


def mp_matmul(a: MPMatrix, b: MPMatrix, c: MPMatrix | None = None, *,
              alpha: float = 1.0, beta: float = 0.0,
              plan: GemmPlan | None = None) -> MPMatrix:
    """C ← α·A·B + β·C routed through the best known execution path.

    With no explicit ``plan``, resolution order is in-memory registry →
    persisted plan cache (``autotune`` winners) → analytical cost model.
    """
    with obs.span("gemm.resolve", "gemm"):
        a, b, c = canonical_operands(a, b, c)
        prob = problem_of(a, b, c, alpha=alpha, beta=beta)
        if plan is None:
            plan, _ = resolve_plan(prob)
        else:
            bad = validate_plan(plan, prob, detect_device())
            if bad:
                raise ValueError(f"plan {plan.key()} invalid: {bad}")
    obs.metrics_registry().counter(
        DISPATCH_METRIC, path=plan.path, op=prob.op,
        formats=prob.formats).inc()
    with obs.span("gemm.dispatch", "gemm", path=plan.path,
                  m=prob.m, n=prob.n, k=prob.k, op=prob.op,
                  formats=prob.formats):
        return execute_plan(plan, a, b, c, alpha=alpha, beta=beta)


# ---------------------------------------------------------------------------
# Distributed SUMMA integration (op = "summa{P}x{Q}")
# ---------------------------------------------------------------------------

#: local-update paths of the distributed SUMMA rank-update
SUMMA_PATHS = ("ref", "grouped")


def summa_problem_from_maps(pa, pb, pc, tile: int, P: int, Q: int,
                            fset=None, *, alpha: float = 1.0,
                            beta: float = 0.0,
                            pad_free: bool = True) -> GemmProblem:
    """Distributed plan-key problem from raw class maps (no operands
    materialized).

    The key carries the mesh shape (in the op tag), the *per-shard* M/N
    extents (tile counts × tile), the full K, and the format-set tag, so a
    plan tuned for one grid/shape/format combination is never served to
    another.  A ``!ub`` op suffix marks C maps that are not shard-balanced
    (the grouped local path is invalid for those)."""
    from repro.core import schedule
    from repro.core.formats import DEFAULT_FORMATS
    fset = fset or DEFAULT_FORMATS
    prob = GemmProblem.from_maps(pa, pb, pc, tile, alpha=alpha, beta=beta,
                                 pad_free=pad_free, fset=fset)
    balanced = schedule.is_shard_balanced(pc, P, Q, fset)
    op = f"summa{P}x{Q}" + ("" if balanced else "!ub")
    return dataclasses.replace(prob, op=op, m=prob.m // P, n=prob.n // Q)


def summa_problem(a: MPMatrix, b: MPMatrix, c: MPMatrix, mesh,
                  axes=("row", "col"), *, alpha: float = 1.0,
                  beta: float = 0.0) -> GemmProblem:
    """Distributed plan-key problem for a SUMMA GEMM on ``mesh``
    (see summa_problem_from_maps for the key anatomy)."""
    row_ax, col_ax = tuple(axes)
    P, Q = mesh.shape[row_ax], mesh.shape[col_ax]
    base = problem_of(a, b, c, alpha=alpha, beta=beta)
    return summa_problem_from_maps(
        a.cls.arr, b.cls.arr, c.cls.arr, a.tile, P, Q, a.fset,
        alpha=alpha, beta=beta, pad_free=base.pad_free)


def resolve_summa_plan(prob: GemmProblem, dev: DeviceSpec | None = None
                       ) -> tuple[GemmPlan, str]:
    """registry > persisted cache > reference path.

    Unlike single-device resolution there is no cost-model fallback: the
    grouped Pallas local update runs only when a tuned plan exists for this
    (mesh, per-shard shape, format set) key; otherwise the reference
    one-dot-per-C-class update is used."""
    dev = dev or detect_device()
    key = S.plan_key(dev, prob)
    hit = _lookup_plan(prob, dev)
    if hit is not None:
        _count_resolution("summa_" + hit[1], key)
        return hit
    t = prob.tile
    _count_resolution("summa_default", key)
    return GemmPlan(path="ref", bm=t, bn=t, bk=t), "default"


def summa_mp_matmul(a: MPMatrix, b: MPMatrix, c: MPMatrix | None = None, *,
                    mesh, axes=("row", "col"), alpha: float = 1.0,
                    beta: float = 0.0, plan: GemmPlan | None = None
                    ) -> MPMatrix:
    """Distributed twin of :func:`mp_matmul`: C ← α·A·B + β·C over ``mesh``
    with the local rank-update routed through the plan registry/cache."""
    from repro.core.summa import summa_mp_gemm
    return summa_mp_gemm(a, b, c, mesh=mesh, axes=axes, alpha=alpha,
                         beta=beta, plan=plan)


def autotune_summa(a: MPMatrix, b: MPMatrix, c: MPMatrix | None = None, *,
                   mesh, axes=("row", "col"), alpha: float = 1.0,
                   beta: float = 0.0, **kw) -> GemmPlan:
    """Measure the SUMMA local-update candidates (ref vs grouped) on this
    mesh and persist the winner under the distributed plan key."""
    from repro.core.summa import summa_mp_gemm
    a, b, c = canonical_operands(a, b, c)
    prob = summa_problem(a, b, c, mesh, axes, alpha=alpha, beta=beta)
    plan, _ = S.autotune_problem(
        prob,
        lambda p: summa_mp_gemm(a, b, c, mesh=mesh, axes=axes, alpha=alpha,
                                beta=beta, plan=p).bufs,
        paths=SUMMA_PATHS, **kw)
    return plan


# ---------------------------------------------------------------------------
# MPLinear integration (op = "linear")
# ---------------------------------------------------------------------------

_LINEAR_PATHS = ("ksplit_xla", "ksplit_pallas")


def linear_problem(w: KSplitWeight, m: int) -> GemmProblem:
    k_cls = w.k_cls.arr
    fset = w.fset
    bh = float((k_cls == fset.high).mean())
    b8 = (float((k_cls == fset.low8).mean())
          if fset.low8 is not None else 0.0)
    k, n = w.shape
    return GemmProblem(
        m=int(m), n=n, k=k, tile=w.tile, op="linear",
        a_high=0.0, a_low8=0.0, b_high=bh, b_low8=b8,
        c_high=0.0, c_low8=0.0, b_k_constant=True,
        c_classes=(fset.low,), has_low8=bool(b8),
        alpha_one=True, beta_zero=True, pad_free=True,
        formats=fset.key())


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kernel_linear(blocks, x2d, w):
    bm, bn, bk = blocks
    return ops.ksplit_matmul_kernel(x2d, w, bm=bm, bn=bn, bk=bk)


def _kernel_linear_fwd(blocks, x2d, w):
    return _kernel_linear(blocks, x2d, w), (x2d, w)


def _kernel_linear_bwd(blocks, res, g):
    # gradients through the XLA ksplit path — numerically the same matmul,
    # and pallas_call has no AD rule of its own
    x2d, w = res
    _, vjp = jax.vjp(ksplit_matmul, x2d, w)
    return vjp(g)


_kernel_linear.defvjp(_kernel_linear_fwd, _kernel_linear_bwd)


def linear_matmul(x, w: KSplitWeight):
    """MPLinear's matmul, with the kernel/block choice taken from the plan
    registry instead of a hardcoded default.

    Resolution is registry/cache only (a miss falls back to the XLA ksplit
    path) so tracing a model never triggers search or measurement; call
    ``tune_linear_params`` once at setup to pre-resolve every layer.
    Batched activations [..., K] are flattened to 2D for the kernel; the
    backward pass runs through the XLA path via custom_vjp.
    """
    m = 1
    for d in x.shape[:-1]:
        m *= int(d)
    dev = detect_device()
    prob = linear_problem(w, m)
    key = S.plan_key(dev, prob)
    plan = _REGISTRY.get(key) or S.default_cache().get(key)
    # the kernel path assumes x's K columns are class-contiguous, which
    # holds iff the K-class vector is sorted by descending code (ratio
    # policies); data-driven unsorted maps stay on the gathering XLA path.
    if (plan is not None and plan.path == "ksplit_pallas"
            and bool(np.all(np.diff(w.k_cls.arr) <= 0))
            and m % plan.bm == 0 and w.shape[1] % plan.bn == 0
            and w.tile % plan.bk == 0):
        obs.metrics_registry().counter(
            DISPATCH_METRIC, path="ksplit_pallas", op="linear",
            formats=w.fset.key()).inc()
        x2d = x.reshape(m, x.shape[-1])
        y = _kernel_linear((plan.bm, plan.bn, plan.bk), x2d, w)
        return y.reshape(*x.shape[:-1], w.shape[1])
    obs.metrics_registry().counter(
        DISPATCH_METRIC, path="ksplit_xla", op="linear",
        formats=w.fset.key()).inc()
    return ksplit_matmul(x, w)


def resolve_plans_for_buckets(params_by_tag: dict, buckets, *,
                              measure: bool = False,
                              cache: S.PlanCache | None = None
                              ) -> dict[tuple, dict[str, GemmPlan]]:
    """Plan-prefetch for the serve scheduler's shape buckets.

    ``buckets`` is an iterable of ``(tag, batch, pad_len)`` where ``tag``
    names a weight variant in ``params_by_tag`` (format-set variants of the
    same architecture).  The serve engine prefills by scanning the decode
    step, so every linear in a bucket runs at ``m = batch`` — one
    resolution per distinct (tag, batch) covers batched prefill and decode
    alike (``pad_len`` is accepted so a future bulk-prefill path can add
    its ``batch * pad_len`` hint without changing callers).

    No other ``m`` occurs on the serving path: refills and the parity
    reference run at the microbatch width too, since a row's result
    depends on the matmul's row count (see ``repro.serve.engine``).

    Returns ``{(tag, batch): {plan_cache_key: GemmPlan}}``; every resolved
    plan is also loaded into the in-memory registry, so the engine's traces
    hit fixed dispatch decisions and never fall back mid-serve."""
    out: dict[tuple, dict[str, GemmPlan]] = {}
    for tag, batch, _pad_len in buckets:
        hint = (tag, int(batch))
        if hint in out:
            continue
        if tag not in params_by_tag:
            raise KeyError(f"unknown weight-variant tag {tag!r} "
                           f"(have {sorted(params_by_tag)})")
        out[hint] = tune_linear_params(params_by_tag[tag], m_hint=batch,
                                       measure=measure, cache=cache)
    return out


# ---------------------------------------------------------------------------
# Refinement-solver integration (op = "solve")
# ---------------------------------------------------------------------------

#: GEMM paths valid for every map structure the solver can produce (ksplit
#: paths need a K-constant B map, which trailing updates never have);
#: ``split`` serves the compute-higher escalation mode, where the HIGH
#: role is a split compound format
SOLVE_PATHS = ("ref", "tile", "grouped", "split")


def solve_gemm_problem(pa: np.ndarray, tile: int, nrhs_t: int,
                       fset) -> GemmProblem:
    """Plan-key problem of the refinement residual GEMM ``A·X``: A carries
    the (escalating) map ``pa``; X and the output are uniform-HIGH
    ``[kt, nrhs_t]`` / ``[mt, nrhs_t]`` (the solution/product must not take
    extra storage rounding).  Solver problems carry ``op="solve"`` so their
    registry entries never collide with ``mp_gemm`` keys."""
    pa = np.asarray(pa)
    pb = np.full((pa.shape[1], nrhs_t), fset.high, np.int8)
    pc = np.full((pa.shape[0], pb.shape[1]), fset.high, np.int8)
    return dataclasses.replace(
        GemmProblem.from_maps(pa, pb, pc, tile, fset=fset), op="solve")


def resolve_solve_plans(a_maps, tile: int, fset, *, nrhs: int,
                        summa_grid: tuple[int, int] | None = None,
                        local_path: str = "ref",
                        paths: Iterable[str] = SOLVE_PATHS,
                        dev: DeviceSpec | None = None) -> dict:
    """Escalation-ladder plan prefetch for the refinement solver
    (``resolve_plans_for_buckets``' twin for ``repro.solve``).

    ``a_maps`` is the ladder of A-matrix class maps the solve can escalate
    through (rung 0 = the starting map).  For every rung this resolves —
    cost model only, never measuring — a plan for the residual GEMM ``A·X``
    and for each blocked-LU trailing-update shape, loads them into the
    in-memory registry under ``op="solve"`` keys, and (with ``summa_grid``)
    registers the distributed residual GEMM under its real
    ``summa{P}x{Q}`` plan key so mid-solve promotion never triggers a
    retune, an un-prefetched fallback, or a recompile.

    Returns ``{("residual", rung): plan, ("trail", step, rung): plan,
    ("summa", rung): plan, "keys": [...]}`` — the solver passes these plans
    explicitly, so a solve issues zero fresh resolutions
    (``fresh_resolutions()``) after this call.
    """
    dev = dev or detect_device()
    if nrhs % tile:
        raise ValueError(f"nrhs={nrhs} must be a multiple of tile={tile}")
    rt = nrhs // tile
    book: dict = {}
    keys: list[str] = []
    for rung, pa in enumerate(a_maps):
        pa = np.asarray(pa)
        mt, kt = pa.shape
        prob = solve_gemm_problem(pa, tile, rt, fset)
        plan, _src = resolve_plan(prob, dev, paths)
        book[("residual", rung)] = plan
        keys.append(S.plan_key(dev, prob))
        # blocked-LU trailing updates: step k multiplies L21 (map column k)
        # by U12 (map row k) into the [mt-k-1, kt-k-1] trailing block
        for k in range(min(mt, kt) - 1):
            pl = pa[k + 1:, k:k + 1]
            pu = pa[k:k + 1, k + 1:]
            tprob = dataclasses.replace(
                GemmProblem.from_maps(
                    pl, pu, np.full((pl.shape[0], pu.shape[1]), fset.high,
                                    np.int8), tile, fset=fset),
                op="solve")
            tplan, _src = resolve_plan(tprob, dev, paths)
            book[("trail", k, rung)] = tplan
            keys.append(S.plan_key(dev, tprob))
        if summa_grid is not None:
            P, Q = summa_grid
            pb = np.full((kt, rt), fset.high, np.int8)
            pc = np.full((mt, rt), fset.high, np.int8)
            sprob = summa_problem_from_maps(pa, pb, pc, tile, P, Q, fset)
            splan = GemmPlan(path=local_path, bm=tile, bn=tile, bk=tile)
            bad = validate_plan(splan, sprob, dev)
            if bad:
                raise ValueError(
                    f"solver SUMMA local path {local_path!r} invalid for "
                    f"rung {rung}: {bad}")
            skey = S.plan_key(dev, sprob)
            register_plan(skey, splan)
            book[("summa", rung)] = splan
            keys.append(skey)
    book["keys"] = keys
    return book


def tune_linear_params(params, m_hint: int, *, measure: bool = False,
                       cache: S.PlanCache | None = None,
                       warmup: int = 1, iters: int = 3) -> dict[str, GemmPlan]:
    """Tune-once-at-setup: resolve a plan for every distinct KSplitWeight
    signature in a parameter tree (serve engine / train step call this).

    ``measure=False`` (the default) is pure model selection + cache lookup —
    cheap enough for every startup.  ``measure=True`` times the candidates
    on synthetic activations and persists winners to the plan cache.
    """
    dev = detect_device()
    cache = cache or S.default_cache()
    plans: dict[str, GemmPlan] = {}
    leaves = jax.tree.leaves(
        params, is_leaf=lambda l: isinstance(l, KSplitWeight))
    for w in leaves:
        if not isinstance(w, KSplitWeight):
            continue
        prob = linear_problem(w, m_hint)
        key = S.plan_key(dev, prob)
        if key in plans:
            continue
        if not measure or S.cache_only():
            plan, _ = resolve_plan(prob, dev, _LINEAR_PATHS)
        else:
            x = jnp.zeros((m_hint, w.shape[0]), jnp.bfloat16)

            def run(plan, x=x, w=w):
                if plan.path == "ksplit_pallas":
                    return ops.ksplit_matmul_kernel(
                        x, w, bm=plan.bm, bn=plan.bn, bk=plan.bk)
                return ksplit_matmul(x, w)

            plan, _ = S.autotune_problem(
                prob, run, dev=dev, paths=_LINEAR_PATHS, cache=cache,
                warmup=warmup, iters=iters)
        _REGISTRY[key] = plan
        plans[key] = plan
    return plans
