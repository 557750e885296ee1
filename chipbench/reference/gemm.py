"""Plain fp64 reference of the tile-centric mixed-precision GEMM.

Semantics (arXiv:2508.14848, Algorithm 1, receiver-side rule): every tile
of A and B is stored rounded to its own format; output tile C(i, j) is
computed with both operands converted to the compute type of C(i, j)'s
format, summed exactly here (fp64), then stored in C(i, j)'s format.

The reference computes a sample of the output drawn from the seed: a few
rows in every tile row crossed with a few columns in every tile column, so
every output tile is checked.  The number compared for each output format
is the largest error over that format's sampled elements, as a share of
the root mean square of the reference there.  The reference is taken
before output storage rounding, so a format's number includes its own
storage error.

The control computes each output format's products one format below it
(fp32 in bf16, bf16 in fp8 e4m3, fp8 in per-tile int4): the step a later
change might be tempted to take.
"""
from __future__ import annotations

import numpy as np

from chipbench.reference import formats as F


def sample(tiles: int, tile: int, per_tile: int,
           g: np.random.Generator) -> np.ndarray:
    """``per_tile`` distinct indices in every tile, drawn by ``g``."""
    return np.concatenate([i * tile + np.sort(g.choice(tile, per_tile,
                                                       replace=False))
                           for i in range(tiles)])


def _by_tile(tile_map: np.ndarray, rows: np.ndarray, cols: np.ndarray,
             tile: int) -> np.ndarray:
    """The tile map's entry for every element of ``rows`` x ``cols``."""
    return tile_map[np.ix_(rows // tile, cols // tile)]


def _stored(x, names, amax, names_amax):
    """Elements rounded to their tile's storage format; the tiles' stored
    absolute maxima come along for the per-tile integer control."""
    out = np.empty(x.shape, np.float64)
    for name in np.unique(names):
        sel = names == name
        out[sel] = F.cast(x[sel], F.storage(str(name)))
    stored_amax = np.empty(amax.shape, np.float64)
    for name in np.unique(names_amax):
        sel = names_amax == name
        stored_amax[sel] = F.cast(amax[sel], F.storage(str(name)))
    return out, stored_amax


def _operational(x, out_format: str, control: bool, amax_el):
    """``x`` in the compute type of ``out_format`` (the control: one
    format below it; per-tile integers use each element's tile maximum)."""
    if not control:
        return F.cast(x, F.compute(out_format))
    low = F.one_below(out_format)
    qmax = F.INT_QMAX.get(low)
    if qmax is None:
        return F.cast(x, F.storage(low))
    scale = np.where(amax_el == 0, 1.0, amax_el / qmax)
    return np.clip(np.rint(x / scale), -qmax, qmax) * scale


def reference(a_rows, b_cols, amax_a, amax_b, pa, pb, pc, tile: int,
              rows, cols, control: bool = False) -> np.ndarray:
    """fp64 ``(A·B)[rows][:, cols]`` under the tile semantics (beta = 0).

    ``a_rows`` = ``A[rows, :]`` and ``b_cols`` = ``B[:, cols]`` in fp32 as
    drawn; ``amax_a``/``amax_b`` are each tile's largest magnitude."""
    k = np.arange(a_rows.shape[1])
    a_st, am_a = _stored(np.asarray(a_rows, np.float64),
                         _by_tile(pa, rows, k, tile), amax_a, pa)
    b_st, am_b = _stored(np.asarray(b_cols, np.float64),
                         _by_tile(pb, k, cols, tile), amax_b, pb)
    am_a_el = np.repeat(am_a[rows // tile], tile, axis=1)
    am_b_el = np.repeat(am_b[:, cols // tile], tile, axis=0)
    sel = _by_tile(pc, rows, cols, tile)
    out = np.zeros((len(rows), len(cols)), np.float64)
    for name in np.unique(sel):
        name = str(name)
        lhs = _operational(a_st, name, control, am_a_el)
        rhs = _operational(b_st, name, control, am_b_el)
        out = np.where(sel == name, lhs @ rhs, out)
    return out


def compare(got, want, pc, tile: int, rows, cols) -> dict:
    """``{format: max |got - want| / rms(want)}`` over each output
    format's sampled elements."""
    sel = _by_tile(pc, rows, cols, tile)
    err = np.abs(np.asarray(got, np.float64) - want)
    out = {}
    for name in np.unique(sel):
        m = sel == name
        rms = float(np.sqrt(np.mean(want[m] ** 2)))
        out[str(name)] = float(err[m].max() / rms)
    return out
