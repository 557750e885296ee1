"""Analytical roofline cost model over (path × block shape × precision map).

``GemmProblem`` captures the static facts of one mixed-precision GEMM (shape,
precision-map tile, per-operand role fractions, the active format set,
structural flags); ``GemmPlan`` is one way to execute it (a kernel path plus
block shape).  ``predict_time`` scores a plan as

    max(compute seconds, HBM seconds) + per-task overhead

where compute is pass-weighted by the registered formats' per-device pass
costs (the paper's dgemm/sgemm cost asymmetry), HBM bytes are *storage*
bytes from the class fractions (the paper's bandwidth saving) with the
classic blocked-GEMM re-fetch factors (A travels N/bn times, B travels M/bm
times), and overhead charges each kernel grid step (dominant in CPU
interpret mode).

``validate_plan`` rejects plans that violate MXU alignment (% 128 on real
TPUs), the device's Pallas dtype support (no float16 on v5e), shape
divisibility, path applicability, or the VMEM working-set
budget — the VMEM formulas previously lived only in kernel docstrings
(kernels/mp_gemm_tile.py, kernels/ksplit_gemm.py, kernels/grouped_gemm.py)
and are centralized here.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.formats import DEFAULT_FORMATS, FormatSet, SplitFormat
from repro.tune.device import DeviceSpec

#: every execution path the dispatcher can route to
PATHS = ("ref", "tile", "grouped", "ksplit_xla", "ksplit_pallas", "split")


def split_c_classes(prob: "GemmProblem") -> tuple[int, ...]:
    """C classes of ``prob`` whose format is a split compound format —
    classes only the ``ref`` oracle and the ``split`` path compute
    correctly (a plain tile dot at the slice dtype would silently drop
    the recovery slices)."""
    fset = prob.fset
    return tuple(c for c in prob.c_classes
                 if isinstance(fset.fmt(c), SplitFormat))


def _fracs(cls_map: np.ndarray, fset: FormatSet) -> tuple[float, float]:
    """(frac_high, frac_low8) of a class map."""
    total = cls_map.size
    f8 = (float((cls_map == fset.low8).sum()) / total
          if fset.low8 is not None else 0.0)
    return (float((cls_map == fset.high).sum()) / total, f8)


@dataclasses.dataclass(frozen=True)
class GemmProblem:
    """Static description of one C ← α·A·B + β·C instance."""

    m: int
    n: int
    k: int
    tile: int
    #: operation tag.  ``mp_gemm``/``linear`` are single-device; distributed
    #: SUMMA problems use ``summa{P}x{Q}`` (the mesh shape is part of the
    #: plan-cache identity; ``m``/``n`` are then *per-shard* extents, and a
    #: ``!ub`` suffix marks a C map that is not shard-balanced)
    op: str = "mp_gemm"
    # per-operand role fractions (D and Q; S is the remainder)
    a_high: float = 0.0
    a_low8: float = 0.0
    b_high: float = 0.0
    b_low8: float = 0.0
    c_high: float = 0.0
    c_low8: float = 0.0
    # structural applicability flags
    b_k_constant: bool = False   # B map constant along N (ksplit layouts)
    c_classes: tuple = (DEFAULT_FORMATS.low,)  # distinct classes in C map
    has_low8: bool = False
    alpha_one: bool = True
    beta_zero: bool = True
    pad_free: bool = True        # logical shapes equal padded tile grid
    #: active format-set key — part of the plan-cache identity, so a plan
    #: tuned for one format combination is never served to another
    formats: str = DEFAULT_FORMATS.key()

    @property
    def fset(self) -> FormatSet:
        return FormatSet.from_key(self.formats)

    @classmethod
    def from_maps(cls, pa: np.ndarray, pb: np.ndarray, pc: np.ndarray,
                  tile: int, *, alpha: float = 1.0, beta: float = 0.0,
                  op: str = "mp_gemm", pad_free: bool = True,
                  fset: FormatSet = DEFAULT_FORMATS) -> "GemmProblem":
        pa, pb, pc = (np.asarray(p) for p in (pa, pb, pc))
        ah, a8 = _fracs(pa, fset)
        bh, b8 = _fracs(pb, fset)
        ch, c8 = _fracs(pc, fset)
        return cls(
            m=pa.shape[0] * tile, n=pb.shape[1] * tile,
            k=pa.shape[1] * tile, tile=tile, op=op,
            a_high=ah, a_low8=a8, b_high=bh, b_low8=b8,
            c_high=ch, c_low8=c8,
            b_k_constant=bool(np.all(pb == pb[:, :1])),
            c_classes=tuple(sorted(int(v) for v in np.unique(pc))),
            has_low8=bool(a8 or b8 or c8),
            alpha_one=(alpha == 1.0), beta_zero=(beta == 0.0),
            pad_free=pad_free, formats=fset.key())

    def ratio_key(self) -> str:
        """Compact class-fraction signature used in plan-cache keys."""
        def one(h, l8):
            a, c = round(100 * h), round(100 * l8)
            return f"{a}D{100 - a - c}S" + (f"{c}Q" if c else "")
        return "|".join((one(self.a_high, self.a_low8),
                         one(self.b_high, self.b_low8),
                         one(self.c_high, self.c_low8)))

    def struct_key(self) -> str:
        """Structural signature: everything path applicability depends on
        beyond shape/ratios.  Two problems with different struct keys must
        never share a cached plan (e.g. beta=0 vs beta!=0 decides whether
        the grouped path is legal at all)."""
        return ("a{}b{}k{}p{}c{}".format(
            int(self.alpha_one), int(self.beta_zero),
            int(self.b_k_constant), int(self.pad_free),
            "".join(str(c) for c in self.c_classes)))

    # -- derived byte/pass facts (role fractions × registered formats) ------
    def _elem_bytes(self, code: int) -> float:
        """Storage bytes/elem of one class including amortized per-tile
        metadata (e.g. the 4-byte fp32 scale of per-tile-scaled integer
        formats, spread over tile² elements)."""
        fset = self.fset
        return (fset.bytes_of(code)
                + fset.meta_bytes_of(code) / float(self.tile * self.tile))

    def bytes_per_elem(self, frac_high: float, frac_low8: float) -> float:
        fset = self.fset
        hb, lb = self._elem_bytes(fset.high), self._elem_bytes(fset.low)
        l8b = (self._elem_bytes(fset.low8)
               if fset.low8 is not None else 0.0)
        return (hb * frac_high + l8b * frac_low8
                + lb * (1.0 - frac_high - frac_low8))

    def stream_bytes_per_elem(self) -> float:
        """Bytes/elem the dense multi-buffer (MPMatrix) layout streams: every
        format's buffer travels, valid tile or not (per-tile scale metadata
        amortized in; zero for plain float formats)."""
        return float(sum(self._elem_bytes(c) for c in self.fset.codes))


@dataclasses.dataclass(frozen=True)
class GemmPlan:
    """One executable choice: path plus Pallas block shape (bm/bn/bk are
    ignored by the XLA paths; the tile path requires bm=bn=bk=tile)."""

    path: str
    bm: int = 128
    bn: int = 128
    bk: int = 128

    def key(self) -> str:
        return f"{self.path}:{self.bm}x{self.bn}x{self.bk}"


def plan_vmem_bytes(plan: GemmPlan, prob: GemmProblem) -> int:
    """Peak fast-memory working set of one kernel instance (double-buffered
    streams; formulas match the kernel docstrings)."""
    t, bm, bn, bk = prob.tile, plan.bm, plan.bn, plan.bk
    s = prob.stream_bytes_per_elem()   # Σ format bytes (multi-buffer stream)
    hb = prob.fset.role_bytes()[0]     # widest (accumulator-sized) buffer
    if plan.path in ("tile", "split"):
        # multi-buffer a/b/c inputs (Σ bytes/elem, double-buffered), fp32
        # scratch, multi-buffer output (split slices are extracted in
        # registers from the streamed buffers — no extra VMEM residency)
        return int(t * t * (s * 2 * 3 + 4 + s))
    if plan.path == "grouped":
        # per class call: one candidate input tile per format for A and B,
        # fp32 scratch, one output tile; double-buffered inputs
        return int(t * t * (2 * s * 2 + 4 + hb))
    if plan.path == "ksplit_pallas":
        # x block + w block + y alias + fp32 scratch, double-buffered
        return (bm * bk + bk * bn + 2 * bm * bn) * 4 * 2
    return 0  # XLA paths: no explicit VMEM contract


def kernel_dtypes(fset: FormatSet) -> set[str]:
    """Names of the dtypes a set's Pallas kernels load, compute or slice
    in (buffer, compute and split-slice dtypes of every format)."""
    out = set()
    for f in fset.formats():
        out.update(np.dtype(d).name
                   for d in (f.buffer_dtype, f.compute_dtype))
        if isinstance(f, SplitFormat):
            out.add(np.dtype(f.slice_dtype).name)
    return out


def validate_plan(plan: GemmPlan, prob: GemmProblem, dev: DeviceSpec,
                  vmem_fraction: float = 0.9) -> list[str]:
    """Reasons this plan cannot run (empty list = valid)."""
    bad: list[str] = []
    if plan.path not in PATHS:
        return [f"unknown path {plan.path!r}"]
    is_summa = prob.op.startswith("summa")
    if is_summa and plan.path not in ("ref", "grouped"):
        return [f"SUMMA local update supports ref/grouped, not "
                f"{plan.path!r}"]
    m, n, k, t = prob.m, prob.n, prob.k, prob.tile
    if plan.path == "ref":
        return bad  # always executable (it is the semantic oracle)

    if plan.path == "tile":
        if (plan.bm, plan.bn, plan.bk) != (t, t, t):
            bad.append(f"tile path requires bm=bn=bk=tile={t}")
        if split_c_classes(prob):
            bad.append("split-compound C classes need the split path "
                       "(tile dot would drop the recovery slices)")
    elif plan.path == "split":
        if (plan.bm, plan.bn, plan.bk) != (t, t, t):
            bad.append(f"split path requires bm=bn=bk=tile={t}")
        if not split_c_classes(prob):
            bad.append("split path needs at least one split-compound C "
                       "class (use the tile path otherwise)")
    elif plan.path in ("ksplit_xla", "ksplit_pallas"):
        if not prob.b_k_constant:
            bad.append("ksplit paths need B map constant along N")
        if len(prob.c_classes) != 1:
            bad.append("ksplit paths need a uniform C map")
        if not prob.pad_free:
            bad.append("ksplit paths need unpadded operands")
        if k % t:
            bad.append(f"K={k} not a multiple of tile={t}")
        if any(isinstance(f, SplitFormat) for f in prob.fset.formats()):
            bad.append("ksplit paths compute at the B-class slice dtype "
                       "and do not support split compound formats")
    if plan.path == "grouped":
        if split_c_classes(prob):
            bad.append("split-compound C classes need the split path "
                       "(grouped dot would drop the recovery slices)")
        if is_summa:
            # the SUMMA scan applies alpha/beta outside the per-step kernel,
            # but a static kernel grid needs equal per-shard C class counts
            if prob.op.endswith("!ub"):
                bad.append("grouped SUMMA local update needs a "
                           "shard-balanced C map")
        elif not (prob.alpha_one and prob.beta_zero):
            bad.append("grouped path computes C=A·B (alpha=1, beta=0)")
    if plan.path == "ksplit_pallas":
        if not prob.beta_zero:
            bad.append("ksplit kernel computes y=x·W (beta=0)")
        if m % plan.bm or n % plan.bn:
            bad.append(f"M×N={m}x{n} not divisible by bm×bn="
                       f"{plan.bm}x{plan.bn}")
        # the kernel clamps bk per class and every class's K-extent is a
        # multiple of tile, so bk must divide tile
        if t % plan.bk:
            bad.append(f"bk={plan.bk} must divide tile={t}")

    if plan.path in ("tile", "grouped", "ksplit_pallas", "split") \
            and not dev.interpret:
        for name, b in (("bm", plan.bm), ("bn", plan.bn), ("bk", plan.bk)):
            if b % dev.alignment:
                bad.append(f"{name}={b} violates MXU alignment "
                           f"% {dev.alignment}")
        refused = kernel_dtypes(prob.fset) & set(dev.pallas_bad_dtypes)
        if refused:
            bad.append(f"{dev.kind} Pallas kernels cannot use "
                       f"{sorted(refused)}")
    vmem = plan_vmem_bytes(plan, prob)
    budget = int(dev.vmem_bytes * vmem_fraction)
    if vmem > budget:
        bad.append(f"VMEM working set {vmem} B exceeds budget {budget} B")
    return bad


def _grid_steps(plan: GemmPlan, prob: GemmProblem) -> int:
    m, n, k, t = prob.m, prob.n, prob.k, prob.tile
    if plan.path in ("tile", "split"):
        return (m // t) * (n // t) * (k // t)
    if plan.path == "grouped":
        # one grid per C class over that class's output tiles × kt
        return (m // t) * (n // t) * (k // t)
    if plan.path == "ksplit_pallas":
        return -(-m // plan.bm) * -(-n // plan.bn) * -(-k // plan.bk)
    return 1  # XLA dispatches


def predict_time(plan: GemmPlan, prob: GemmProblem, dev: DeviceSpec) -> dict:
    """Roofline score.  Returns the breakdown; ``total_s`` is the rank key."""
    m, n, k = prob.m, prob.n, prob.k
    fset = prob.fset
    flops = 2.0 * m * n * k
    a_bytes = m * k * prob.bytes_per_elem(prob.a_high, prob.a_low8)
    b_bytes = k * n * prob.bytes_per_elem(prob.b_high, prob.b_low8)
    c_bytes = m * n * prob.bytes_per_elem(prob.c_high, prob.c_low8)

    if plan.path == "ref":
        # one dense dot per distinct C class over the full MNK
        w = sum(dev.format_cost(fset.names[c]) for c in prob.c_classes)
        compute = flops * w
        hbm = len(prob.c_classes) * (m * k + k * n) * 4.0 + 2 * m * n * 4.0
    elif plan.path in ("tile", "split"):
        # operational precision = C tile class (paper Algorithm 1); the
        # split path's slices² low-precision passes are priced by the
        # compound format's registered pass_cost inside class_weight
        w = dev.class_weight(prob.c_high, prob.c_low8, fset)
        compute = flops * w
        # multi-buffer layout streams EVERY format buffer (Σ bytes/elem);
        # blocked re-fetch: A read n/bn times, B read m/bm times
        s = prob.stream_bytes_per_elem()
        hbm = (m * k * s * (n // plan.bn)
               + k * n * s * (m // plan.bm) + 2 * m * n * s)
    elif plan.path == "grouped":
        w = dev.class_weight(prob.c_high, prob.c_low8, fset)
        compute = flops * w
        # storage bytes + the redundant zero-tile streams (×nf), re-fetched
        # once per C class present
        refetch = len(prob.c_classes)
        nf = len(fset)
        hbm = float(nf) * refetch * (a_bytes + b_bytes) + 2 * c_bytes
    else:  # ksplit paths: operational precision = B K-block class
        w = dev.class_weight(prob.b_high, prob.b_low8, fset)
        compute = flops * w
        if plan.path == "ksplit_pallas":
            hbm = (a_bytes * (n // plan.bn) + b_bytes * (m // plan.bm)
                   + 2 * m * n * 4.0)
        else:
            hbm = a_bytes + b_bytes + 2 * m * n * 4.0
    compute_s = compute / (dev.low_tflops * 1e12)
    hbm_s = hbm / (dev.hbm_gbps * 1e9)
    overhead_s = dev.task_overhead_s * _grid_steps(plan, prob)
    return {
        "compute_s": compute_s,
        "hbm_s": hbm_s,
        "overhead_s": overhead_s,
        "vmem_bytes": plan_vmem_bytes(plan, prob),
        "total_s": max(compute_s, hbm_s) + overhead_s,
    }
