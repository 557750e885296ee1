"""Residual-driven adaptive-precision iterative refinement.

HPL-MxP recovers full accuracy from a low-precision LU via iterative
refinement; SGEMM-cube recovers GEMM accuracy on low-precision engines.
This module composes both ideas on the tile-centric stack: the operator is
an :class:`~repro.core.layout.MPMatrix` whose per-tile precision map
*adapts to the observed residual* —

1. factor the quantized operator with blocked LU whose trailing updates run
   through ``tune.mp_matmul`` (``repro.solve.lu``), or use Jacobi-CG for
   SPD systems;
2. refine: the residual GEMM ``A·X`` runs through the same dispatch stack
   at the tile map's precisions; corrections come from the factors;
3. after each sweep, the fp64 oracle metric
   ``||Ax-b|| / (||A||·||x||·n·u_HIGH)`` (``core.accuracy.hpl_mxp_metric``)
   decides convergence; on a stall, tiles whose storage-rounding residual
   contribution exceeds their registry-derived budget
   (``core.accuracy.promotion_mask``) are promoted one role (Q→S→D), the
   layout is re-quantized in place (recovering the dropped bits from the
   exact operator), and the operator is refactored.

Every plan the solve can need — the residual GEMM and every trailing-update
shape, for every escalation rung — is prefetched up front
(``tune.dispatch.resolve_solve_plans``), so promotion never triggers a
mid-solve retune; ``tune.dispatch.fresh_resolutions()`` audits that.

Escalation modes
----------------
``"tile"`` promotes exactly the over-budget tiles (fully data-driven;
single-device).  ``"balanced"`` quantizes promotion to sorted-balanced
ladder rungs (identical per-segment class counts, classes sorted within
panels) — the static-SPMD family distributed SUMMA requires — promoting per
rung the worst per-segment over-budget count.  With ``summa_grid=(P, Q)``
the residual GEMM runs on a P×Q device grid under the prefetched
``summa{P}x{Q}`` plan keys; with the ``grouped`` local path it is
bitwise-identical to the single-device grouped path, so single-device and
distributed solves agree bit-for-bit.
"""
from __future__ import annotations

import dataclasses
import time

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import accuracy as ACC
from repro.core.formats import DEFAULT_FORMATS, FormatSet
from repro.core.layout import MPMatrix
from repro.core.precision import (Policy, make_map, map_ratio_string,
                                  map_storage_bytes, role_class_vector)
from repro.solve import lu as LU
from repro.split.recovery import split_variant
from repro.tune import dispatch as TD
from repro.tune import search as TS
from repro.tune.costmodel import GemmPlan

#: escalation-ladder rungs prefetched for the data-driven ("tile") mode
LADDER_RUNGS = 5

#: most promoted-tile coordinates kept per escalation record (the count is
#: always exact; coordinates of a huge promotion wave are truncated)
PROMOTION_COORD_CAP = 128


@dataclasses.dataclass(frozen=True)
class SolveConfig:
    """Knobs of one adaptive-precision solve."""

    tile: int = 16
    fset: FormatSet = DEFAULT_FORMATS
    ratio_high: float = 0.0        # starting D fraction (HPL-MxP: 0)
    ratio_low8: float = 0.0        # starting Q fraction
    seed: int = 0
    #: acceptance threshold on the HPL-MxP metric ||Ax-b||/(||A||·||x||·n·u)
    #: with u = the HIGH storage roundoff.  HPL-MxP accepts 16 at fp64's
    #: u=2^-52; with fp32 HIGH that is nearly vacuous, so the default is the
    #: classical backward-stability bound (metric ≤ 1).
    tol: float = 1.0
    max_sweeps: int = 60
    max_escalations: int = 32
    #: escalation budget as a fraction of the acceptance threshold: a tile
    #: is promoted when its rounding contribution would push the converged
    #: HPL-MxP metric above ``budget_margin · tol`` (worst-row sum of all
    #: at-budget tiles ≈ tol·margin — promotion stops exactly when the map
    #: is precise enough for the stopping criterion, not at uniform-HIGH)
    budget_margin: float = 0.25
    stall_ratio: float = 0.5       # required per-sweep metric shrink
    method: str = "lu"             # "lu" | "cg" (SPD, Jacobi-preconditioned)
    #: rung-0 map policy: "ratio" (random, the paper's Fig. 2 style) or
    #: "norm_topk" (data-driven — Q tiles land on the quietest tiles, so a
    #: narrow-range format never saturates on the operator's loud entries)
    start_policy: str = "norm_topk"
    cg_check_every: int = 8
    escalation: str = "tile"       # "tile" | "balanced" (SUMMA-compatible)
    #: compute-higher escalation: "store" keeps the classic Q→S→D storage
    #: ladder; "split" swaps the HIGH role for ``split_format`` so stalled
    #: tiles escalate into split-accumulate compute recovery instead of
    #: wider storage; "auto" prices the top-rung residual GEMM both ways
    #: with the cost model and takes the cheaper (single-device only)
    compute_escalation: str = "store"  # "store" | "split" | "auto"
    #: split compound format the compute-higher mode substitutes for HIGH
    split_format: str = "split2_fp16"
    #: shard segments of the balanced ladder; defaults to summa_grid's P.
    #: A single-device run that must match a P×Q distributed solve
    #: bit-for-bit sets this to P so both walk the identical map ladder.
    balance_groups: int | None = None
    #: pad the RHS block to exactly this many columns (must be a multiple
    #: of the padding quantum).  A single-device run compared bit-for-bit
    #: against a P×Q one must pin this to the distributed run's width
    #: (tile·Q multiple) — the padded GEMM extent is part of the trajectory.
    nrhs_pad: int | None = None
    summa_grid: tuple[int, int] | None = None
    local_path: str = "ref"        # SUMMA local-update path (ref | grouped)
    residual_path: str | None = None   # force the single-device GEMM path
    warm: bool = True              # pre-trace the SUMMA escalation ladder


@dataclasses.dataclass
class SolveReport:
    converged: bool
    method: str
    sweeps: int
    escalations: int
    factorizations: int
    metric: float
    metric_history: list
    ratio_history: list
    final_ratio: str
    final_map: np.ndarray
    storage_bytes: int
    uniform_high_bytes: int
    gemm_seconds: float
    total_seconds: float
    gemm_fraction: float
    fresh_resolutions: int
    summa_recompiles: int
    plan_keys: int
    x: np.ndarray
    #: wall-clock seconds of each refinement sweep (CG: each
    #: ``cg_check_every`` iteration block)
    sweep_seconds: list = dataclasses.field(default_factory=list)
    #: one record per escalation: promoted-tile coordinates (capped at
    #: :data:`PROMOTION_COORD_CAP`), tile count, rung, resulting ratio
    promotions: list = dataclasses.field(default_factory=list)
    #: compute-higher escalation outcome: "store" (classic storage ladder)
    #: or "split" (HIGH role replaced by a split compound format)
    compute_mode: str = "store"
    #: cost-model price (seconds) of the top-rung residual GEMM under
    #: storage promotion vs split-accumulate recovery; NaN when the
    #: decision did not run (``compute_escalation="store"``)
    store_cost_s: float = float("nan")
    split_cost_s: float = float("nan")


def _balanced_map(mt: int, nt: int, n_hi: int, n_lo8: int, groups: int,
                  fset: FormatSet) -> np.ndarray:
    """Sorted-balanced ladder map: every shard segment of every tile-column
    holds ``n_hi`` HIGH / ``n_lo8`` LOW8 tiles, classes sorted by descending
    storage cost (what ``core.summa`` requires of A operands)."""
    seg = mt // groups
    col = role_class_vector(n_hi, seg - n_hi - n_lo8, n_lo8, fset)
    return np.tile(np.tile(col, groups)[:, None], (1, nt))


def _groups(cfg: SolveConfig) -> int:
    if cfg.balance_groups is not None:
        return cfg.balance_groups
    return cfg.summa_grid[0] if cfg.summa_grid else 1


def _ladder(cfg: SolveConfig, mt: int, nt: int,
            weights: np.ndarray | None = None) -> list[np.ndarray]:
    """Every A-map the escalation can visit (rung 0 = the starting map;
    later rungs are representative maps the plan prefetch resolves
    against)."""
    if cfg.escalation == "balanced":
        groups = _groups(cfg)
        if mt % groups:
            raise ValueError(
                f"balance_groups={groups} must divide the tile-row count "
                f"{mt} (N/tile) for sorted-balanced ladder maps")
        seg = mt // groups
        h0 = int(round(cfg.ratio_high * seg))
        q0 = int(round(cfg.ratio_low8 * seg))
        return [_balanced_map(mt, nt, h, min(q0, seg - h), groups, cfg.fset)
                for h in range(h0, seg + 1)]
    f0 = cfg.ratio_high
    maps = []
    for r in range(LADDER_RUNGS):
        fh = f0 + (1.0 - f0) * r / (LADDER_RUNGS - 1)
        fq = min(cfg.ratio_low8, 1.0 - fh)
        kind = cfg.start_policy if r == 0 else "ratio"
        pol = Policy(kind=kind, ratio_high=fh, ratio_low8=fq, seed=cfg.seed)
        maps.append(make_map((mt * cfg.tile, nt * cfg.tile), cfg.tile, pol,
                             weights=weights if kind == "norm_topk" else None,
                             fset=cfg.fset))
    return maps


def _tile_rung(cfg: SolveConfig, frac_high: float) -> int:
    """Nearest prefetched ladder rung for a data-driven map's D fraction."""
    f0 = cfg.ratio_high
    if f0 >= 1.0:
        return LADDER_RUNGS - 1
    r = (frac_high - f0) / (1.0 - f0) * (LADDER_RUNGS - 1)
    return int(np.clip(round(r), 0, LADDER_RUNGS - 1))


def _rung_cost_s(fset: FormatSet, mt: int, rt: int, tile: int) -> float:
    """Cost-model price of the *top-rung* residual GEMM ``A·X`` (uniform
    HIGH — the map every storage ladder saturates at) under ``fset``.

    Ranks :data:`~repro.tune.dispatch.SOLVE_PATHS` candidates directly
    (model only, no cache writes, no fresh-resolution counts) and returns
    the best predicted seconds."""
    dev = TD.detect_device()
    hi = np.full((mt, mt), fset.high, np.int8)
    prob = TD.solve_gemm_problem(hi, tile, rt, fset)
    cands = TS.candidate_plans(prob, dev, TD.SOLVE_PATHS)
    if not cands:
        return float("inf")
    return float(TS.rank_plans(cands, prob, dev)[0][1]["total_s"])


def _decide_compute(cfg: SolveConfig, mt: int, rt: int
                    ) -> tuple[SolveConfig, str, float, float]:
    """Compute-higher escalation decision: keep the storage ladder (HIGH =
    the set's widest storage format) or substitute the split compound
    format, so a stalled tile escalates into slices² low-precision passes
    instead of wider storage.  ``"auto"`` takes whichever the cost model
    prices cheaper at the ladder's top rung; both prices are recorded in
    the report either way."""
    if cfg.compute_escalation not in ("store", "split", "auto"):
        raise ValueError(
            f"unknown compute_escalation {cfg.compute_escalation!r} "
            "(store | split | auto)")
    if cfg.compute_escalation == "store":
        return cfg, "store", float("nan"), float("nan")
    if cfg.summa_grid is not None:
        raise ValueError(
            "compute_escalation needs a single-device solve (the SUMMA "
            "local paths do not run split compound formats)")
    split_fset = split_variant(cfg.fset, cfg.split_format)
    store_s = _rung_cost_s(cfg.fset, mt, rt, cfg.tile)
    split_s = _rung_cost_s(split_fset, mt, rt, cfg.tile)
    mode = ("split" if cfg.compute_escalation == "split"
            or split_s < store_s else "store")
    if mode == "split":
        cfg = dataclasses.replace(cfg, fset=split_fset)
    if obs.is_enabled():
        obs.event("solve.compute_decision", "solve", mode=mode,
                  policy=cfg.compute_escalation, store_s=store_s,
                  split_s=split_s)
    return cfg, mode, store_s, split_s


def _summa_cache_size() -> int:
    """Compiled SUMMA executables so far (the recompile audit's counter)."""
    from repro.core.summa import _summa_impl
    return int(_summa_impl._cache_size())


class _Solver:
    """State shared by the LU and CG drivers."""

    def __init__(self, a, b, cfg: SolveConfig):
        t = cfg.tile
        self.cfg = cfg
        self.a64 = np.asarray(a, np.float64)
        n = self.a64.shape[0]
        if self.a64.shape != (n, n) or n % t:
            raise ValueError(f"operator must be square with N % tile == 0, "
                             f"got {self.a64.shape} tile {t}")
        b2 = np.asarray(b, np.float64).reshape(n, -1)
        self.nrhs_logical = b2.shape[1]
        # pad the RHS block to the tile (and SUMMA column) granularity
        quantum = t * (cfg.summa_grid[1] if cfg.summa_grid else 1)
        nrhs = -(-self.nrhs_logical // quantum) * quantum
        if cfg.nrhs_pad is not None:
            if cfg.nrhs_pad < nrhs or cfg.nrhs_pad % quantum:
                raise ValueError(
                    f"nrhs_pad={cfg.nrhs_pad} must be a multiple of "
                    f"{quantum} covering the {self.nrhs_logical} RHS "
                    "columns")
            nrhs = cfg.nrhs_pad
        self.b64 = np.zeros((n, nrhs))
        self.b64[:, : self.nrhs_logical] = b2
        self.n, self.nrhs = n, nrhs
        self.mt, self.rt = n // t, nrhs // t

        # compute-higher escalation: possibly swap the HIGH role for the
        # split compound format before any layout/ladder/plan exists, so
        # the whole solve (prefetch included) runs under one format set
        cfg, self.compute_mode, self.store_cost_s, self.split_cost_s = (
            _decide_compute(cfg, self.mt, self.rt))
        self.cfg = cfg

        if cfg.summa_grid:
            P, Q = cfg.summa_grid
            if cfg.escalation != "balanced":
                raise ValueError(
                    "summa_grid needs escalation='balanced' (SUMMA requires "
                    "sorted-balanced maps; per-tile promotion breaks them)")
            if n % (P * t) or nrhs % (Q * t) or self.mt % P or self.mt % Q:
                raise ValueError(
                    f"N={n}, nrhs={nrhs} incompatible with the {P}x{Q} grid "
                    f"at tile {t} (need N % (P·t) == nrhs % (Q·t) == 0 and "
                    f"K-panels divisible by both grid extents)")
            from repro.launch.mesh import make_grid_mesh
            self.mesh = make_grid_mesh(P, Q)
        else:
            self.mesh = None

        self.a32 = jnp.asarray(self.a64.astype(np.float32))
        self.ladder = _ladder(cfg, self.mt, self.mt, weights=self.a64)
        self.pa = self.ladder[0].copy()
        self.rung = 0
        self.A = MPMatrix.from_dense(self.a32, self.pa, t, cfg.fset)
        self.x_map = np.full((self.mt, self.rt), cfg.fset.high, np.int8)
        self.zero_c = MPMatrix.from_dense(
            jnp.zeros((n, nrhs)), self.x_map, t, cfg.fset)
        self.gemm_seconds = 0.0
        self.escalations = 0
        self.factorizations = 0
        self.ratio_history: list[str] = []
        self.sweep_seconds: list[float] = []
        self.promotions: list[dict] = []
        # ---- ladder prefetch: every plan the solve can need -------------
        self.book = TD.resolve_solve_plans(
            self.ladder, t, cfg.fset, nrhs=nrhs, summa_grid=cfg.summa_grid,
            local_path=cfg.local_path)
        self._x_mp = MPMatrix.from_dense(
            jnp.zeros((n, nrhs)), self.x_map, t, cfg.fset)
        if self.mesh is not None and cfg.warm:
            # pre-trace every rung of the escalation ladder so promotion
            # never compiles mid-solve
            for pa in self.ladder:
                aw = MPMatrix.from_dense(self.a32, pa, t, cfg.fset)
                self._amul_summa(aw)
        self.recompiles0 = _summa_cache_size()
        # snapshot (not reset) the process-global counters: concurrent
        # solves or other dispatch users must not clobber each other's
        # audit; the report computes the delta over this solve
        self._fresh0 = TD.fresh_resolutions()

    # -- GEMMs through the dispatch stack ---------------------------------
    def _amul_summa(self, a_mp: MPMatrix) -> np.ndarray:
        from repro.core.summa import summa_mp_gemm
        x = self._x_mp
        out = summa_mp_gemm(a_mp, x, self.zero_c, mesh=self.mesh)
        return np.asarray(out.to_dense())

    def amul(self, x32: np.ndarray) -> np.ndarray:
        """A·X at the tile map's precisions (the refinement inner GEMM)."""
        t0 = time.perf_counter()
        self._x_mp = MPMatrix.from_dense(
            jnp.asarray(x32, jnp.float32), self.x_map, self.cfg.tile,
            self.cfg.fset)
        if self.mesh is not None:
            out = self._amul_summa(self.A)
        else:
            if self.cfg.residual_path is not None:
                plan = GemmPlan(path=self.cfg.residual_path,
                                bm=self.cfg.tile, bn=self.cfg.tile,
                                bk=self.cfg.tile)
            else:
                plan = self.book[("residual", self._book_rung())]
            out = np.asarray(TD.mp_matmul(
                self.A, self._x_mp, self.zero_c, plan=plan).to_dense())
        self.gemm_seconds += time.perf_counter() - t0
        return out

    def _book_rung(self) -> int:
        if self.cfg.escalation == "balanced":
            return self.rung
        return _tile_rung(self.cfg,
                          float((self.pa == self.cfg.fset.high).mean()))

    def factor(self) -> np.ndarray:
        """Blocked LU of the current quantized operator; trailing updates
        via mp_matmul under the prefetched per-step plans."""
        cfg, t = self.cfg, self.cfg.tile
        rung = self._book_rung()
        a_stored = np.asarray(self.A.to_dense())

        def trailing(l21, u12, step):
            t0 = time.perf_counter()
            pl = self.pa[step + 1:, step:step + 1]
            pu = self.pa[step:step + 1, step + 1:]
            lmp = MPMatrix.from_dense(jnp.asarray(l21), pl, t, cfg.fset)
            ump = MPMatrix.from_dense(jnp.asarray(u12), pu, t, cfg.fset)
            cmp_ = MPMatrix.from_dense(
                jnp.zeros((l21.shape[0], u12.shape[1])),
                np.full((pl.shape[0], pu.shape[1]), cfg.fset.high, np.int8),
                t, cfg.fset)
            out = TD.mp_matmul(lmp, ump, cmp_,
                               plan=self.book[("trail", step, rung)])
            prod = np.asarray(out.to_dense())
            self.gemm_seconds += time.perf_counter() - t0
            return prod

        with obs.span("solve.factor", "solve", rung=rung,
                      factorization=self.factorizations + 1):
            lu_, _stats = LU.blocked_lu(a_stored, self.pa, t, trailing)
        self.factorizations += 1
        return lu_

    # -- escalation ---------------------------------------------------------
    def escalate(self, x: np.ndarray) -> bool:
        """Promote over-budget tiles one role and re-quantize the operator
        from the exact fp64 values.  Returns False when there is nothing
        left to promote (map saturated at HIGH)."""
        cfg, fset = self.cfg, self.cfg.fset
        old_pa = self.pa
        xa = x if np.all(np.isfinite(x)) else np.ones_like(x)
        # budget slack derived from the acceptance threshold: at-budget
        # tiles sum (worst row) to a metric of budget_margin·tol < tol
        slack = cfg.tol * cfg.budget_margin * self.n
        mask = ACC.promotion_mask(self.a64, np.asarray(self.A.to_dense()),
                                  xa, self.pa, cfg.tile, fset, slack)
        if cfg.escalation == "balanced":
            groups = _groups(cfg)
            seg = self.mt // groups
            per_seg = mask.reshape(groups, seg, self.mt).sum(axis=1)
            step = max(1, int(per_seg.max()))
            if self.rung >= len(self.ladder) - 1:
                return False
            self.rung = min(self.rung + step, len(self.ladder) - 1)
            self.pa = self.ladder[self.rung].copy()
        else:
            if not mask.any():
                # residual-driven fallback: nothing exceeds its budget but
                # refinement stalled — promote the worst decile by
                # contribution/budget ratio so progress is still made
                contrib = ACC.tile_rounding_contribution(
                    self.a64, np.asarray(self.A.to_dense()), xa, cfg.tile)
                budget = ACC.escalation_threshold(
                    self.a64, xa, cfg.tile, fset, slack)
                ratio = np.where(self.pa < fset.high,
                                 contrib / np.maximum(budget, 1e-300), -1.0)
                k = max(1, int(0.1 * ratio.size))
                idx = np.argsort(ratio, axis=None)[::-1][:k]
                mask = np.zeros_like(self.pa, bool)
                mask.flat[idx] = True
                mask &= self.pa < fset.high
            if not mask.any():
                return False
            self.pa = self.pa + mask.astype(np.int8)
        self.A = self.A.requantize(self.pa, dense=self.a32)
        self.escalations += 1
        ratio = map_ratio_string(self.pa, fset)
        self.ratio_history.append(ratio)
        changed = np.argwhere(self.pa != old_pa)
        self.promotions.append({
            "escalation": self.escalations,
            "mode": cfg.escalation,
            "rung": self._book_rung(),
            "tiles": int(len(changed)),
            "coords": [[int(i), int(j)]
                       for i, j in changed[:PROMOTION_COORD_CAP]],
            "ratio": ratio,
        })
        if obs.is_enabled():
            obs.event("solve.escalate", "solve",
                      escalation=self.escalations, mode=cfg.escalation,
                      rung=self._book_rung(), tiles=int(len(changed)),
                      ratio=ratio)
        return True

    def metric(self, x: np.ndarray) -> float:
        return ACC.hpl_mxp_metric(self.a64, x, self.b64, self.cfg.fset)

    def report(self, x, converged, sweeps, history, t0) -> SolveReport:
        cfg = self.cfg
        uniform = np.full_like(self.pa, cfg.fset.high)
        total = time.perf_counter() - t0
        return SolveReport(
            converged=bool(converged), method=cfg.method, sweeps=sweeps,
            escalations=self.escalations,
            factorizations=self.factorizations,
            metric=float(history[-1]) if history else float("inf"),
            metric_history=[float(v) for v in history],
            ratio_history=list(self.ratio_history),
            final_ratio=map_ratio_string(self.pa, cfg.fset),
            final_map=self.pa.copy(),
            storage_bytes=map_storage_bytes(self.pa, cfg.tile, cfg.fset),
            uniform_high_bytes=map_storage_bytes(uniform, cfg.tile,
                                                 cfg.fset),
            gemm_seconds=self.gemm_seconds, total_seconds=total,
            gemm_fraction=self.gemm_seconds / max(total, 1e-12),
            fresh_resolutions=TD.fresh_resolutions() - self._fresh0,
            summa_recompiles=_summa_cache_size() - self.recompiles0,
            plan_keys=len(self.book["keys"]),
            x=x[:, : self.nrhs_logical],
            sweep_seconds=[float(v) for v in self.sweep_seconds],
            promotions=list(self.promotions),
            compute_mode=self.compute_mode,
            store_cost_s=float(self.store_cost_s),
            split_cost_s=float(self.split_cost_s))


def _robust_factor(sv: _Solver):
    """Factor, escalating past tiles whose storage format killed a pivot
    (e.g. fp8 saturation on a loud diagonal block)."""
    ones = np.ones((sv.n, sv.nrhs))
    while True:
        try:
            return sv.factor()
        except ZeroDivisionError:
            if (sv.escalations >= sv.cfg.max_escalations
                    or not sv.escalate(ones)):
                raise


def _solve_lu(sv: _Solver, t0: float) -> SolveReport:
    cfg = sv.cfg
    lu_ = _robust_factor(sv)
    x = np.zeros((sv.n, sv.nrhs))
    history: list[float] = []
    prev = float("inf")
    sweeps = 0
    while sweeps < cfg.max_sweeps:
        ts = time.perf_counter()
        with obs.span("solve.sweep", "solve", sweep=sweeps + 1,
                      method="lu"):
            r = sv.b64 - np.asarray(sv.amul(x.astype(np.float32)),
                                    np.float64)
            d = LU.solve_upper(
                lu_,
                LU.solve_unit_lower(lu_, r.astype(np.float32), cfg.tile),
                cfg.tile)
            x = x + d
            m = sv.metric(x)
        sv.sweep_seconds.append(time.perf_counter() - ts)
        sweeps += 1
        history.append(m)
        if obs.is_enabled():
            obs.event("solve.sweep_metric", "solve", sweep=sweeps,
                      metric=float(m))
        if m <= cfg.tol:
            return sv.report(x, True, sweeps, history, t0)
        if not np.isfinite(m) or m > cfg.stall_ratio * prev:
            if (sv.escalations >= cfg.max_escalations
                    or not sv.escalate(x)):
                break
            lu_ = _robust_factor(sv)   # factors follow the escalated map
            if not np.all(np.isfinite(x)) or not np.isfinite(m):
                x = np.zeros_like(x)   # restart a diverged iterate
            prev = float("inf")
            continue
        prev = m
    return sv.report(x, False, sweeps, history, t0)


def _solve_cg(sv: _Solver, t0: float) -> SolveReport:
    """Jacobi-preconditioned CG for SPD operators, matvecs through the
    tile-centric GEMM; escalation restarts from the current iterate."""
    cfg = sv.cfg
    dinv = 1.0 / np.clip(np.abs(np.diag(sv.a64)), 1e-300, None)

    def restart(x):
        r = sv.b64 - np.asarray(sv.amul(x.astype(np.float32)), np.float64)
        z = dinv[:, None] * r
        return r, z, z.copy(), (r * z).sum(axis=0)

    x = np.zeros((sv.n, sv.nrhs))
    r, z, p, rz = restart(x)
    history: list[float] = []
    prev = float("inf")
    iters = 0
    blk0 = time.perf_counter()
    while iters < cfg.max_sweeps * cfg.cg_check_every:
        v = np.asarray(sv.amul(p.astype(np.float32)), np.float64)
        alpha = rz / np.clip((p * v).sum(axis=0), 1e-300, None)
        x = x + alpha[None, :] * p
        r = r - alpha[None, :] * v
        z = dinv[:, None] * r
        rz_new = (r * z).sum(axis=0)
        p = z + (rz_new / np.clip(rz, 1e-300, None))[None, :] * p
        rz = rz_new
        iters += 1
        if iters % cfg.cg_check_every:
            continue
        m = sv.metric(x)
        # one "sweep" = one cg_check_every iteration block
        sv.sweep_seconds.append(time.perf_counter() - blk0)
        blk0 = time.perf_counter()
        history.append(m)
        if obs.is_enabled():
            obs.event("solve.sweep_metric", "solve", sweep=iters,
                      metric=float(m))
        if m <= cfg.tol:
            return sv.report(x, True, iters, history, t0)
        if not np.isfinite(m) or m > cfg.stall_ratio * prev:
            if (sv.escalations >= cfg.max_escalations
                    or not sv.escalate(x)):
                break
            if not np.all(np.isfinite(x)) or not np.isfinite(m):
                x = np.zeros_like(x)
            r, z, p, rz = restart(x)   # the operator changed
            prev = float("inf")
            continue
        prev = m
    return sv.report(x, False, iters, history, t0)


def solve(a, b, cfg: SolveConfig = SolveConfig()) -> SolveReport:
    """Solve ``A·x = b`` with residual-driven adaptive tile precision.

    ``a`` is the exact operator (any float dtype; quantization to the tile
    map is this function's job), ``b`` one or more right-hand sides.  The
    returned report carries the solution, the escalated map and its storage
    bytes, the HPL-MxP metric trajectory, and the zero-mid-solve-retune
    audit counters.
    """
    t0 = time.perf_counter()
    with obs.span("solve.run", "solve", method=cfg.method, tile=cfg.tile,
                  escalation=cfg.escalation):
        sv = _Solver(a, b, cfg)
        sv.ratio_history.append(map_ratio_string(sv.pa, cfg.fset))
        if cfg.method == "cg":
            return _solve_cg(sv, t0)
        if cfg.method != "lu":
            raise ValueError(f"unknown method {cfg.method!r} (lu | cg)")
        return _solve_lu(sv, t0)
