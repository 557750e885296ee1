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

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.formats import DEFAULT_FORMATS, FormatSet, SplitFormat
from repro.core.layout import MPMatrix, _pad_to, dense_of, tile_buffers

#: traces of the ``ref`` GEMM's device program, labelled by the C classes
#: present (``classes=bf16+fp32``); steady-state calls add none
REF_TRACES_METRIC = "gemm.ref_traces"


def _class_dot(ad: jax.Array, bd: jax.Array, fmt) -> jax.Array:
    """One C-class dense dot at the class's operational precision —
    split compound formats expand to their slices² pair products."""
    if isinstance(fmt, SplitFormat):
        from repro.split.recovery import split_dot_general
        return split_dot_general(ad, bd, fmt)
    op = fmt.compute_dtype
    # materialize the dot's operands and result: inside a jitted caller
    # XLA fuses the operands' producers (buffer sums, converts) into the
    # dot, rerunning them at every operand-tile load, and the select and
    # store into its output; a 16384³ bf16 dot on a TPU v5e then took 181
    # ms (64 ms with the producers alone kept out) against 47 ms alone
    ad, bd = jax.lax.optimization_barrier((ad.astype(op), bd.astype(op)))
    return jax.lax.optimization_barrier(jax.lax.dot_general(
        ad, bd, (((1,), (0,)), ((), ())),
        precision=fmt.dot_precision,
        preferred_element_type=jnp.float32))


@partial(jax.jit, static_argnames=("classes", "tile", "shape", "fset",
                                   "alpha", "beta"))
def _ref_program(a_bufs, b_bufs, c_bufs, c_map, *, classes, tile, shape,
                 fset, alpha, beta):
    """The device program of :func:`mp_gemm_ref`.  ``c_map`` is C's int8
    ``[mt, nt]`` class map as a traced argument, so a new map with the same
    ``classes`` present reuses the compiled program."""
    # Python here runs once per trace, not per call
    obs.metrics_registry().counter(
        REF_TRACES_METRIC,
        classes="+".join(fset.fmt(cc).name for cc in classes)).inc()
    ad, bd, cd = dense_of(a_bufs), dense_of(b_bufs), dense_of(c_bufs)
    mt, nt = c_map.shape
    sel = c_map[:, None, :, None]
    out = jnp.zeros((mt, tile, nt, tile), jnp.float32)
    for cc in classes:
        upd = alpha * _class_dot(ad, bd, fset.fmt(cc)) + beta * cd
        out = jnp.where(sel == cc, upd.reshape(out.shape), out)
    out = out.reshape(cd.shape)
    # store back in C's per-tile precision
    wp = _pad_to(out[: shape[0], : shape[1]], *cd.shape)
    return tile_buffers(wp, c_map, tile, fset)


def mp_gemm_ref(a: MPMatrix, b: MPMatrix, c: MPMatrix,
                alpha: float = 1.0, beta: float = 0.0) -> MPMatrix:
    """One dense dot per C-precision class present, then a per-tile
    select: numerically exact w.r.t. the tile semantics, and the path the
    cost model picks for the benchmark's 16384³ GEMMs on a TPU v5e.

    The host reads which classes C's map holds; everything else runs as
    one compiled program (:func:`_ref_program`), the map expanded on the
    device.  ``gemm.ref_traces{classes=…}`` counts its traces."""
    classes = tuple(int(v) for v in np.unique(c.cls.arr))
    bufs = _ref_program(
        tuple(a.bufs), tuple(b.bufs), tuple(c.bufs),
        jnp.asarray(c.cls.arr, jnp.int8), classes=classes, tile=c.tile,
        shape=c.shape, fset=c.fset, alpha=alpha, beta=beta)
    return MPMatrix(bufs, c.cls, c.tile, c.shape, c.fset)


def mp_gemm_tilewise_ref(a: MPMatrix, b: MPMatrix, c: MPMatrix,
                         alpha: float = 1.0, beta: float = 0.0) -> jax.Array:
    """Slow literal per-tile loop (Algorithm 1 verbatim) in numpy/jnp, used
    to validate mp_gemm_ref itself in tests.  Returns dense fp32."""
    t = c.tile
    fset = c.fset
    ad, bd, cd = (np.asarray(m.padded_dense()) for m in (a, b, c))
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
