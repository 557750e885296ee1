"""Tiled precision-conversion kernel — the paper's "datatype conversion task".

Receiver-side conversion sometimes has to materialize (layout changes,
checkpoint import, policy re-mapping).  This kernel streams a matrix through
VMEM tile by tile and rewrites it in the target dtype.  Pure bandwidth; block
(bm, bn) = (256, 256) keeps the double-buffered working set ≈ 1.5 MB.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl


def _convert_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "bm", "bn", "interpret"))
def convert(x, *, out_dtype, bm: int = 256, bn: int = 256,
            interpret: bool = False):
    """Tiled dtype conversion: x[M, N] -> out_dtype[M, N]."""
    M, N = x.shape
    bm = min(bm, M)
    bn = min(bn, N)
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    return pl.pallas_call(
        _convert_kernel,
        grid=(M // bm, N // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
    )(x)
