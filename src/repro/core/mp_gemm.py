"""Reference tile-centric mixed-precision GEMM (Algorithm 1 of the paper).

Defines the *semantic contract* that every performant path (Pallas kernels,
SUMMA, KSplit matmuls) is validated against:

    C ← α·A·B + β·C

where A, B, C carry independent per-tile precision maps.  The operational
precision of the update task for output tile C(i,j) is the precision class of
C(i,j) (receiver-side conversion: A/B tiles arrive in their storage precision
and are converted to the task's precision at the consumer).  Accumulation is
always fp32 (the paper's SGEMM accumulates in fp32 registers; TPU MXU
accumulates fp32 natively).

The operational dtype / dot precision / storage rounding of each class come
from the operands' :class:`~repro.core.formats.FormatSet` — there is no
parallel dtype table here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.formats import DEFAULT_FORMATS, FormatSet, SplitFormat
from repro.core.layout import MPMatrix


def _class_dot(ad: jax.Array, bd: jax.Array, fmt) -> jax.Array:
    """One C-class dense dot at the class's operational precision —
    split compound formats expand to their slices² pair products."""
    if isinstance(fmt, SplitFormat):
        from repro.split.recovery import split_dot_general
        return split_dot_general(ad, bd, fmt)
    op = fmt.compute_dtype
    return jax.lax.dot_general(
        ad.astype(op), bd.astype(op), (((1,), (0,)), ((), ())),
        precision=fmt.dot_precision,
        preferred_element_type=jnp.float32)


def _storage_dense(m: MPMatrix) -> jax.Array:
    """Padded dense fp32 view with per-tile storage rounding applied."""
    return m.padded_dense()


def _expand(cls_map: np.ndarray, tile: int) -> np.ndarray:
    return np.repeat(np.repeat(cls_map, tile, 0), tile, 1)


def mp_gemm_ref(a: MPMatrix, b: MPMatrix, c: MPMatrix,
                alpha: float = 1.0, beta: float = 0.0) -> MPMatrix:
    """Oracle implementation: one dense dot per C-precision class present,
    then a per-tile select.  Numerically exact w.r.t. the tile semantics;
    not the performance path (classes × MNK flops)."""
    with obs.span("gemm.storage_view", "gemm"):
        ad, bd = _storage_dense(a), _storage_dense(b)
        cd = _storage_dense(c)
    fset = c.fset
    classes = sorted({int(v) for v in np.unique(c.cls.arr)})
    per_class = {}
    for cc in classes:
        fmt = fset.fmt(cc)
        with obs.span("gemm.class_dot", "gemm", format=fmt.name):
            acc = _class_dot(ad, bd, fmt)
            per_class[cc] = alpha * acc + beta * cd
    with obs.span("gemm.expand_map", "gemm"):
        host_sel = _expand(c.cls.arr, c.tile)
    with obs.span("gemm.upload_map", "gemm"):
        sel = jnp.asarray(host_sel)
    with obs.span("gemm.select", "gemm"):
        out = jnp.zeros_like(cd)
        for cc in classes:
            out = jnp.where(sel == cc, per_class[cc], out)
    # store back in C's per-tile precision
    with obs.span("gemm.store", "gemm"):
        return MPMatrix.from_dense(
            out[: c.shape[0], : c.shape[1]], c.cls.arr, c.tile, fset)


def mp_gemm_tilewise_ref(a: MPMatrix, b: MPMatrix, c: MPMatrix,
                         alpha: float = 1.0, beta: float = 0.0) -> jax.Array:
    """Slow literal per-tile loop (Algorithm 1 verbatim) in numpy/jnp, used
    to validate mp_gemm_ref itself in tests.  Returns dense fp32."""
    t = c.tile
    fset = c.fset
    ad, bd, cd = map(np.asarray, (_storage_dense(a), _storage_dense(b),
                                  _storage_dense(c)))
    mt, kt = a.cls.arr.shape
    kt2, nt = b.cls.arr.shape
    assert kt == kt2
    out = np.zeros_like(cd)
    for i in range(mt):
        for j in range(nt):
            fmt = fset.fmt(int(c.cls.arr[i, j]))
            op = fmt.compute_dtype
            acc = np.zeros((t, t), np.float32)
            for l in range(kt):
                at = ad[i * t:(i + 1) * t, l * t:(l + 1) * t]
                bt = bd[l * t:(l + 1) * t, j * t:(j + 1) * t]
                if isinstance(fmt, SplitFormat):
                    acc += np.asarray(_class_dot(
                        jnp.asarray(at), jnp.asarray(bt), fmt), np.float32)
                    continue
                # receiver-side conversion to operational precision
                at_op = np.asarray(jnp.asarray(at).astype(op), np.float32)
                bt_op = np.asarray(jnp.asarray(bt).astype(op), np.float32)
                acc += at_op @ bt_op
            upd = alpha * acc + beta * cd[i * t:(i + 1) * t, j * t:(j + 1) * t]
            # storage rounding of the C tile (one tile -> one scale block)
            out[i * t:(i + 1) * t, j * t:(j + 1) * t] = np.asarray(
                fmt.roundtrip(jnp.asarray(upd)))
    return jnp.asarray(out[: c.shape[0], : c.shape[1]])


def model_flops(m: int, n: int, k: int) -> int:
    """Useful FLOPs of the GEMM (2MNK) independent of precision classes."""
    return 2 * m * n * k


def mxu_weighted_flops(c_cls: np.ndarray, m: int, n: int, k: int,
                       fset: FormatSet = DEFAULT_FORMATS,
                       device_kind: str = "tpu-v5e") -> float:
    """FLOPs weighted by MXU pass count per C-tile class — the quantity a
    real accelerator must execute (HIGH = 3 bf16 passes on v5e)."""
    total = c_cls.size
    w = sum(fset.fmt(int(v)).cost_on(device_kind)
            for v in c_cls.reshape(-1)) / total
    return 2.0 * m * n * k * w
