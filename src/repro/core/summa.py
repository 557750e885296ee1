"""Distributed tile-centric mixed-precision GEMM — SUMMA over shard_map.

Implements the paper's Algorithm 1 dataflow on a P×Q device grid:

  for each k-panel l:
      owner column of A(:, l) broadcasts the panel along grid rows
      owner row    of B(l, :) broadcasts the panel along grid columns
      every shard rank-updates its C block at the C tiles' precision

**Receiver-side conversion over the ICI** (the paper's key communication
property): panels are communicated *in storage precision* — one slab per
registered format in the operands' :class:`~repro.core.formats.FormatSet`
(the fp32 tiles of a panel travel as an fp32 slab, the bf16 tiles as a bf16
slab, the fp8 tiles as an fp8 slab, …); the receiver upcasts after the
collective.  For this to have static shapes under SPMD, the A/B class maps
must be *sorted-balanced* (``schedule.sorted_balanced_map``): within every
panel and every shard segment, classes appear in descending storage cost
(``fset.class_order``) and every panel has identical per-class counts.  This
is the static-SPMD adaptation of PaRSEC's per-message datatypes.

The C map may be any per-tile map.  The local rank-update is routed through
the same plan machinery as single-device ``mp_matmul``
(``repro.tune.dispatch.resolve_summa_plan``): with a tuned plan the update
runs the grouped Pallas kernel (``kernels/grouped_gemm``, interpret-mode on
CPU) fed per-shard dispatch tables; otherwise it falls back to the reference
one-dot-per-C-class update.  Distributed plans are cached under keys that
carry the mesh shape, the per-shard tile counts, and the format-set tag.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as Pspec

from repro.core.formats import DEFAULT_FORMATS, FormatSet

#: local-update paths the SUMMA rank-update can execute
LOCAL_PATHS = ("ref", "grouped")


def _panel_owner_steps(K: int, tile: int, P: int, Q: int):
    """Static per-step metadata: owner col of A panel, local panel index in
    the owner, owner row of B panel, local panel index.

    Raises a descriptive ``ValueError`` when the K panels do not divide
    evenly over the grid (the old code silently mis-sliced panels)."""
    if K % tile:
        raise ValueError(f"K={K} must be a multiple of tile={tile}")
    kt = K // tile
    if kt % Q or kt % P:
        raise ValueError(
            f"K/tile={kt} panels do not divide evenly over the {P}x{Q} "
            f"grid (kt%P={kt % P}, kt%Q={kt % Q}); choose K a multiple of "
            f"tile*P and tile*Q so every shard owns whole panels")
    kloc_a, kloc_b = K // Q, K // P
    q_a = (np.arange(kt) * tile) // kloc_a
    la = np.arange(kt) - q_a * (kloc_a // tile)
    p_b = (np.arange(kt) * tile) // kloc_b
    lb = np.arange(kt) - p_b * (kloc_b // tile)
    return (q_a.astype(np.int32), la.astype(np.int32),
            p_b.astype(np.int32), lb.astype(np.int32))


def _check_sorted_balanced(cls_map: np.ndarray, axis: int, groups: int,
                           fset: FormatSet) -> dict[int, int]:
    """Verify the map is sorted-balanced along ``axis`` with ``groups`` shard
    segments: within every segment-panel the classes appear in descending
    storage cost (``fset.class_order``) with identical per-class counts.
    Returns the per-class tile count of one segment-panel."""
    m = cls_map if axis == 0 else cls_map.T
    if m.shape[0] % groups:
        raise ValueError(
            f"map extent {m.shape[0]} along axis {axis} not divisible by "
            f"{groups} shard groups")
    seg = m.shape[0] // groups
    counts: tuple | None = None
    for g in range(groups):
        blk = m[g * seg:(g + 1) * seg]
        for j in range(m.shape[1]):
            col = blk[:, j]
            c = {code: int((col == code).sum()) for code in fset.codes}
            canon = np.concatenate(
                [np.full(c[code], code, np.int8)
                 for code in fset.class_order])
            if not np.array_equal(col, canon):
                raise ValueError(
                    "map not class-sorted (descending storage cost) within "
                    "panel segment — build A/B maps with "
                    "schedule.sorted_balanced_map")
            key = tuple(c[code] for code in fset.codes)
            if counts is None:
                counts = key
            elif counts != key:
                raise ValueError(
                    "map not balanced across panels/segments — per-panel "
                    "class counts must be identical for static SPMD slabs")
    return {code: (counts[code] if counts else 0) for code in fset.codes}


def _class_offsets(counts: dict[int, int], tile: int, fset: FormatSet
                   ) -> dict[int, int]:
    """Element offset of each class's slab within a local panel, in
    ``class_order`` (descending storage cost — matching the sorted maps)."""
    off, out = 0, {}
    for code in fset.class_order:
        out[code] = off
        off += counts[code] * tile
    return out


def _segment_class_vector(counts: dict[int, int], fset: FormatSet
                          ) -> np.ndarray:
    """Per-tile class codes of one sorted segment-panel (class_order)."""
    return np.concatenate([np.full(counts[code], code, np.int8)
                           for code in fset.class_order])


def _panel_slot_tables(vec: np.ndarray, fset: FormatSet, transpose: bool
                      ) -> list[np.ndarray]:
    """Grouped-kernel dispatch tables for a sorted panel: per format code, a
    table routing tile index → slot in that format's tile stack (or the
    trailing zero tile on a class mismatch)."""
    out = []
    for code in fset.codes:
        n_code = int((vec == code).sum())
        tbl = np.full((len(vec), 1), n_code, np.int32)
        rows = np.nonzero(vec == code)[0]
        tbl[rows, 0] = np.arange(len(rows), dtype=np.int32)
        out.append(tbl.T.copy() if transpose else tbl)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("cls_a", "cls_b", "cls_c", "tile", "mesh", "axes",
                     "alpha", "beta", "fset", "local_path"))
def _summa_impl(a_bufs, b_bufs, c_bufs, *, cls_a, cls_b, cls_c, tile, mesh,
                axes, alpha, beta, fset=DEFAULT_FORMATS, local_path="ref"):
    row_ax, col_ax = axes
    P = mesh.shape[row_ax]
    Q = mesh.shape[col_ax]
    M, K = a_bufs[0].shape
    N = b_bufs[0].shape[1]
    T = tile
    nf = len(fset)
    if M % (P * T) or N % (Q * T):
        raise ValueError(
            f"M={M}, N={N} must be multiples of P*tile={P * T} and "
            f"Q*tile={Q * T} for the {P}x{Q} grid")
    mloc, nloc = M // P, N // Q
    if local_path not in LOCAL_PATHS:
        raise ValueError(f"unknown SUMMA local path {local_path!r}; "
                         f"valid: {LOCAL_PATHS}")

    amap, bmap, cmap = cls_a.arr, cls_b.arr, cls_c.arr
    a_cnt = _check_sorted_balanced(amap, axis=0, groups=P, fset=fset)
    b_cnt = _check_sorted_balanced(bmap, axis=1, groups=Q, fset=fset)
    a_off = _class_offsets(a_cnt, T, fset)   # row offset of each A slab
    b_off = _class_offsets(b_cnt, T, fset)   # col offset of each B slab
    c_classes = sorted(int(v) for v in np.unique(cmap))

    steps = _panel_owner_steps(K, T, P, Q)
    sel_c = np.repeat(np.repeat(cmap, T, 0), T, 1)  # int8[M, N]

    # ---- grouped-path static prep (dispatch tables, per-shard C coords) ----
    tables = ()
    table_specs = ()
    if local_path == "grouped":
        from repro.kernels.grouped_gemm import _grouped_class_call
        from repro.kernels.mp_gemm_tile import format_specs
        from repro.kernels.ops import _interpret
        mt_loc, nt_loc = mloc // T, nloc // T
        a_vec = _segment_class_vector(a_cnt, fset)
        b_vec = _segment_class_vector(b_cnt, fset)
        a_slots = tuple(jnp.asarray(t) for t in
                        _panel_slot_tables(a_vec, fset, transpose=False))
        b_slots = tuple(jnp.asarray(t) for t in
                        _panel_slot_tables(b_vec, fset, transpose=True))
        specs = format_specs(fset)
        interpret = _interpret()
        # per-shard (ci, cj) coordinate tables, stacked host-side; counts
        # must be identical across shards (shard-balanced C map) so the
        # kernel grid is static under SPMD
        n_per_class: dict[int, int] = {}
        stacked = []
        for code in c_classes:
            n_c = None
            ci = cj = None
            for p in range(P):
                for q in range(Q):
                    blk = cmap[p * mt_loc:(p + 1) * mt_loc,
                               q * nt_loc:(q + 1) * nt_loc]
                    idx = np.argwhere(blk == code).astype(np.int32)
                    if n_c is None:
                        n_c = len(idx)
                        ci = np.zeros((P, Q, n_c), np.int32)
                        cj = np.zeros((P, Q, n_c), np.int32)
                    elif len(idx) != n_c:
                        raise ValueError(
                            "grouped SUMMA local path needs a shard-balanced "
                            "C map (identical per-class tile counts on every "
                            "shard, e.g. schedule.balanced_ratio_map with "
                            f"{P}x{Q} groups); class {code} varies")
                    ci[p, q], cj[p, q] = idx[:, 0], idx[:, 1]
            n_per_class[code] = int(n_c or 0)
            stacked.append((jnp.asarray(ci), jnp.asarray(cj)))
        tables = tuple(stacked)
        tspec = Pspec(row_ax, col_ax)
        table_specs = tuple((tspec, tspec) for _ in c_classes)

    def local_fn(a_bufs, b_bufs, c_bufs, sel_c, tables, steps):
        col = jax.lax.axis_index(col_ax)
        row = jax.lax.axis_index(row_ax)

        def bcast(x, owner, axis_name):
            if x.size == 0:
                return x
            x = jnp.where(owner == (col if axis_name == col_ax else row), x,
                          jnp.zeros_like(x))
            return jax.lax.psum(x, axis_name)

        def ref_update(a_slabs, b_slabs):
            # receiver-side conversion: upcast every storage slab, then one
            # dot per C class at that class's operational precision
            a_panel = jnp.concatenate(
                [a_slabs[c].astype(jnp.float32) for c in fset.class_order],
                axis=0)
            b_panel = jnp.concatenate(
                [b_slabs[c].astype(jnp.float32) for c in fset.class_order],
                axis=1)
            upd = jnp.zeros((mloc, nloc), jnp.float32)
            for code in c_classes:
                fmt = fset.fmt(code)
                op = jnp.dtype(fmt.compute_dtype)
                d = jax.lax.dot_general(
                    a_panel.astype(op), b_panel.astype(op),
                    (((1,), (0,)), ((), ())),
                    precision=fmt.dot_precision,
                    preferred_element_type=jnp.float32)
                upd = d if len(c_classes) == 1 else jnp.where(
                    sel_c == code, d, upd)
            return upd

        def grouped_update(a_slabs, b_slabs, tables):
            # storage slabs → per-format tile stacks (+ trailing zero tile);
            # the Pallas kernel does the receiver-side upcast in registers
            a_tiles, b_tiles = [], []
            for code in fset.codes:
                dt = fset.storage_dtype(code)
                z = jnp.zeros((1, T, T), dt)
                na, nb = a_cnt[code], b_cnt[code]
                ta = (a_slabs[code].reshape(na, T, T) if na
                      else jnp.zeros((0, T, T), dt))
                tb = (b_slabs[code].reshape(T, nb, T).transpose(1, 0, 2)
                      if nb else jnp.zeros((0, T, T), dt))
                a_tiles.append(jnp.concatenate([ta, z], 0))
                b_tiles.append(jnp.concatenate([tb, z], 0))
            upd = jnp.zeros((mt_loc, nt_loc, T, T), jnp.float32)
            for i, code in enumerate(c_classes):
                ci, cj = (t.reshape(-1) for t in tables[i])
                # fp32 output spec: per-step partials accumulate outside the
                # kernel; C-tile storage rounding happens once at the end
                spec = (specs[code][0], specs[code][1], "float32")
                out = _grouped_class_call(
                    tuple(a_tiles), tuple(b_tiles), ci, cj,
                    a_slots, b_slots, tile=T, interpret=interpret,
                    meta=(n_per_class[code], 1, spec))
                upd = upd.at[ci, cj].add(out)
            return upd.transpose(0, 2, 1, 3).reshape(mloc, nloc)

        def step(acc, s):
            qa, la, pb, lb = s
            # --- panels ship one slab per registered format ----------------
            a_slabs, b_slabs = {}, {}
            for code in fset.codes:
                rows = a_cnt[code] * T
                sl = jax.lax.dynamic_slice(
                    a_bufs[code], (a_off[code], la * T), (rows, T))
                a_slabs[code] = bcast(sl, qa, col_ax)
                cols = b_cnt[code] * T
                sl = jax.lax.dynamic_slice(
                    b_bufs[code], (lb * T, b_off[code]), (T, cols))
                b_slabs[code] = bcast(sl, pb, row_ax)
            # --- local rank-T update via the resolved plan -----------------
            if local_path == "grouped":
                upd = grouped_update(a_slabs, b_slabs, tables)
            else:
                upd = ref_update(a_slabs, b_slabs)
            return acc + upd, None

        acc0 = jnp.zeros((mloc, nloc), jnp.float32)
        acc, _ = jax.lax.scan(step, acc0, steps)
        c32 = c_bufs[0].astype(jnp.float32)
        for b in c_bufs[1:]:
            c32 = c32 + b.astype(jnp.float32)
        out = alpha * acc + beta * c32
        # store back in each C tile's storage precision (one buffer/format)
        return tuple(
            jnp.where(sel_c == code, out, 0.0).astype(fset.storage_dtype(code))
            for code in fset.codes)

    spec2 = Pspec(row_ax, col_ax)
    rep = Pspec()
    # replication checking off: pallas_call has no replication rule, and
    # the psum-broadcast carry is device-varying
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=((spec2,) * nf, (spec2,) * nf, (spec2,) * nf, spec2,
                  table_specs, (rep,) * 4),
        out_specs=(spec2,) * nf, check_vma=False,
    )(tuple(a_bufs), tuple(b_bufs), tuple(c_bufs), jnp.asarray(sel_c),
      tables, tuple(map(jnp.asarray, steps)))


def summa_mp_gemm(a, b, c=None, *, mesh, axes: Sequence[str] = ("row", "col"),
                  alpha: float = 1.0, beta: float = 0.0, plan=None):
    """Distributed C ← αAB + βC over ``mesh`` with MPMatrix operands.

    Works for any registered :class:`~repro.core.formats.FormatSet` (2 or 3
    formats): panels travel as one storage-precision slab per format.  A/B
    maps must be sorted-balanced (see module docstring); ``c=None`` defaults
    to a zero uniform-LOW output like single-device ``mp_matmul``.

    The local rank-update path comes from ``plan`` (a
    :class:`~repro.tune.costmodel.GemmPlan` whose ``path`` is ``"ref"`` or
    ``"grouped"``) or, when omitted, from the distributed plan registry/cache
    (``repro.tune.dispatch.resolve_summa_plan`` — reference path on a miss).
    Returns a new MPMatrix with C's class map.
    """
    from repro import obs
    from repro.core.layout import MPMatrix
    from repro.tune import dispatch as _dispatch

    a, b, c = _dispatch.canonical_operands(a, b, c)
    fset = a.fset
    prob = _dispatch.summa_problem(a, b, c, mesh, axes=tuple(axes),
                                   alpha=alpha, beta=beta)
    if plan is None:
        plan, _src = _dispatch.resolve_summa_plan(prob)
    else:
        from repro.tune.costmodel import validate_plan
        from repro.tune.device import detect_device
        bad = validate_plan(plan, prob, detect_device())
        if bad:
            raise ValueError(f"SUMMA plan {plan.key()} invalid: {bad}")
    obs.metrics_registry().counter(
        _dispatch.DISPATCH_METRIC, path=plan.path, op=prob.op,
        formats=prob.formats).inc()

    # host-side lens on the device-side panel loop: one span for the whole
    # distributed GEMM plus, with JSONL tracing on, an instant per k-panel
    # carrying the *static* owner schedule (the scan body itself runs under
    # jit/SPMD, so per-step wall-clock is not observable from here — the
    # schedule is)
    row_ax, col_ax = tuple(axes)
    K = prob.k                      # padded K = tile-grid extent × tile
    with obs.span("summa.gemm", "summa", op=prob.op, path=plan.path,
                  m=prob.m, n=prob.n, k=prob.k, formats=prob.formats,
                  steps=K // a.tile):
        if obs.is_enabled():
            try:
                qa, la, pb, lb = _panel_owner_steps(
                    K, a.tile, mesh.shape[row_ax], mesh.shape[col_ax])
                for s in range(len(qa)):
                    obs.event("summa.panel", "summa", step=s,
                              a_owner_col=int(qa[s]), a_local=int(la[s]),
                              b_owner_row=int(pb[s]), b_local=int(lb[s]))
            except ValueError:
                pass     # _summa_impl raises the descriptive error below
        out_bufs = _summa_impl(
            tuple(a.bufs), tuple(b.bufs), tuple(c.bufs),
            cls_a=a.cls, cls_b=b.cls, cls_c=c.cls, tile=a.tile, mesh=mesh,
            axes=tuple(axes), alpha=alpha, beta=beta, fset=fset,
            local_path=plan.path)
        return MPMatrix(tuple(out_bufs), c.cls, c.tile, c.shape, fset)


def summa_collective_bytes(M: int, N: int, K: int, tile: int, P: int, Q: int,
                           ratio_high: float, ratio_low8: float = 0.0,
                           fset: FormatSet = DEFAULT_FORMATS) -> dict:
    """Analytic communication model (per full GEMM, all shards summed):
    each of K/tile steps broadcasts an A panel (M/P rows) to Q columns and a
    B panel (N/Q cols) to P rows, in storage precision — the per-element wire
    cost is the role-fraction-weighted storage bytes of the format set."""
    kt = K // tile
    hb, lb, l8b = fset.role_bytes()
    bytes_per_elem = (hb * ratio_high + l8b * ratio_low8
                      + lb * (1.0 - ratio_high - ratio_low8))
    a_panel = (M // P) * tile * bytes_per_elem
    b_panel = (N // Q) * tile * bytes_per_elem
    per_step = a_panel * P * Q + b_panel * P * Q   # every shard receives one
    return {
        "steps": kt,
        "a_panel_bytes": a_panel,
        "b_panel_bytes": b_panel,
        "total_bytes": per_step * kt,
        "bytes_per_elem_model": bytes_per_elem,
    }


def config_selfcheck(cfg, grid) -> dict:
    """``summa_selfcheck`` at an ArchConfig's tile/policy/format set on a
    fresh P×Q grid mesh — the shared launch wiring behind
    ``launch.train --summa`` and ``serve.Engine(summa_grid=…)``."""
    from repro.core.formats import format_set
    from repro.launch.mesh import make_grid_mesh
    P, Q = (int(v) for v in grid)
    return summa_selfcheck(
        make_grid_mesh(P, Q), tile=cfg.mp_tile, policy=cfg.mp_policy,
        fset=format_set(*cfg.mp_formats.split("+")))


def summa_selfcheck(mesh, *, tile: int = 16, size: int | None = None,
                    policy=None, fset: FormatSet = DEFAULT_FORMATS,
                    axes: Sequence[str] = ("row", "col"), seed: int = 0
                    ) -> dict:
    """Launch-time validation of the distributed path (train/serve wiring):
    build a sorted-balanced GEMM at the config's tile/policy/format set, run
    SUMMA on ``mesh`` against the single-device reference, and return a
    report (resolved plan, relative error, wire-byte model)."""
    from repro.core import schedule
    from repro.core.layout import MPMatrix
    from repro.core.mp_gemm import mp_gemm_ref
    from repro.core.precision import Policy
    from repro.tune import dispatch as _dispatch

    row_ax, col_ax = tuple(axes)
    P, Q = mesh.shape[row_ax], mesh.shape[col_ax]
    policy = policy or Policy(kind="ratio", ratio_high=0.5)
    size = size or tile * P * Q          # divides every grid constraint
    M = N = K = size
    mt, nt, kt = M // tile, N // tile, K // tile
    pa = schedule.sorted_balanced_map(mt, kt, policy, axis=0, groups=P,
                                      fset=fset)
    pb = schedule.sorted_balanced_map(kt, nt, policy, axis=1, groups=Q,
                                      fset=fset)
    pc = schedule.balanced_ratio_map(mt, nt, policy, P, Q, fset=fset)
    key = jax.random.PRNGKey(seed)
    ka, kb, kc = jax.random.split(key, 3)
    A = MPMatrix.from_dense(jax.random.normal(ka, (M, K)), pa, tile, fset)
    B = MPMatrix.from_dense(jax.random.normal(kb, (K, N)), pb, tile, fset)
    C = MPMatrix.from_dense(jnp.zeros((M, N)), pc, tile, fset)
    prob = _dispatch.summa_problem(A, B, C, mesh, axes=tuple(axes))
    plan, source = _dispatch.resolve_summa_plan(prob)
    out = summa_mp_gemm(A, B, C, mesh=mesh, axes=axes, plan=plan)
    ref = mp_gemm_ref(A, B, C)
    err = float(jnp.abs(out.to_dense() - ref.to_dense()).max())
    scale = float(jnp.abs(ref.to_dense()).max())
    hi = float((pa == fset.high).mean())
    lo8 = (float((pa == fset.low8).mean()) if fset.low8 is not None else 0.0)
    model = summa_collective_bytes(M, N, K, tile, P, Q, hi, lo8, fset)
    return {
        "grid": f"{P}x{Q}", "size": size, "tile": tile,
        "formats": fset.key(), "local_path": plan.path,
        "plan_source": source, "max_abs_err": err,
        "rel_err": err / max(scale, 1e-30),
        "wire_bytes_per_elem": model["bytes_per_elem_model"],
    }
