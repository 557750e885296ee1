"""Tile-heterogeneous matrix layouts.

A JAX array has a single dtype, so the paper's "each tile has its own
precision" needs an explicit representation.  Three layouts (see DESIGN.md §3):

* ``MPMatrix``        — dense-multi: one dense buffer per format in the
                        active FormatSet, each tile valid in exactly one.
                        Semantic/reference layout: simple, differentiable,
                        composable.
* ``CompactMPMatrix`` — class-sorted compact tiles; storage bytes are exactly
                        the paper's 4·a + 2·b (+ 1·c) per element.
* ``KSplitWeight``    — production layout for LM matmuls: the class map is
                        constant along N, the K-blocks are permuted so each
                        class is contiguous, and matmul lowers to one dense
                        dot per format with zero HLO-FLOP inflation.

Which formats the buffers hold is driven by the layout's
:class:`~repro.core.formats.FormatSet` (default ``fp8_e4m3+bf16+fp32``);
class-map entries are codes into that set.  The legacy ``hi``/``lo``/``lo8``
(and ``w_hi``/``w_lo``/``w_lo8``) accessors remain as role-based views.

All are registered pytrees; static metadata (maps, tile size, format set)
lives in numpy/aux on the host and is hashed into jit keys.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import precision as P
from repro.core.formats import DEFAULT_FORMATS, FormatSet


def _pad_to(x: jax.Array, m: int, n: int) -> jax.Array:
    pm, pn = m - x.shape[0], n - x.shape[1]
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


def _check_codes(cls_map: np.ndarray, fset: FormatSet) -> np.ndarray:
    cls_map = np.asarray(cls_map)
    bad = [int(c) for c in np.unique(cls_map) if not 0 <= c < len(fset)]
    if bad:
        raise ValueError(f"class codes {bad} outside format set {fset.names}")
    return cls_map


class _HashableMap:
    """numpy array wrapped to be hashable/eq-comparable as jit static data."""

    __slots__ = ("arr", "_key")

    def __init__(self, arr: np.ndarray):
        self.arr = np.ascontiguousarray(arr)
        self.arr.setflags(write=False)
        self._key = (self.arr.shape, self.arr.dtype.str, self.arr.tobytes())

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _HashableMap) and self._key == other._key

    def __repr__(self):
        return f"_HashableMap{self.arr.shape}"


def dense_of(bufs) -> jax.Array:
    """Sum of per-format buffers at fp32: the padded dense view of an
    ``MPMatrix`` (each tile is nonzero in one buffer only)."""
    d = bufs[0].astype(jnp.float32)
    for b in bufs[1:]:
        d = d + b.astype(jnp.float32)
    return d


def tile_buffers(wp: jax.Array, cmap: jax.Array, tile: int,
                 fset: FormatSet) -> tuple[jax.Array, ...]:
    """One buffer per format code of ``fset`` for the padded fp32 ``wp``:
    the tiles whose class in ``cmap`` (an int8 ``[mt, nt]`` device array,
    traced or not) is that code go through the format's ``to_buffer``,
    every other tile is zero.  The map is compared over a ``(mt, t, nt, t)``
    view of ``wp``, so no element-wide map is built."""
    mt, nt = cmap.shape
    w4 = wp.reshape(mt, tile, nt, tile)
    sel = cmap[:, None, :, None]
    return tuple(
        fset.fmt(code).to_buffer(
            jnp.where(sel == code, w4, 0.0).reshape(wp.shape), tile=tile)
        for code in fset.codes)


# ---------------------------------------------------------------------------
# MPMatrix — dense per-format buffers
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MPMatrix:
    """Dense multi-buffer tile-heterogeneous matrix.

    ``bufs[code]`` is a full (padded) dense buffer in that format's storage
    dtype; tile (i, j) is valid in the buffer selected by ``cls[i, j]`` and
    zero elsewhere.
    """

    bufs: tuple[jax.Array, ...]   # one [M, N] buffer per format code
    cls: _HashableMap             # int8[mt, nt]  (static)
    tile: int                     # static
    shape: tuple[int, int]        # logical (unpadded) shape, static
    fset: FormatSet = DEFAULT_FORMATS   # static

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        # buffers are direct children (not a nested tuple): optimizer /
        # error-feedback code maps leaves to (value, residual) tuples and
        # splits them with is_leaf=isinstance(tuple), which must not fire
        # on the container of the buffers themselves
        return tuple(self.bufs), (self.cls, self.tile, self.shape, self.fset)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(tuple(children), *aux)

    # -- role views (legacy accessors) --------------------------------------
    @property
    def hi(self) -> jax.Array:
        return self.bufs[self.fset.high]

    @property
    def lo(self) -> jax.Array:
        return self.bufs[self.fset.low]

    @property
    def lo8(self) -> jax.Array:
        if self.fset.low8 is None:
            return jnp.zeros(self.padded_shape, jnp.float8_e4m3fn)
        return self.bufs[self.fset.low8]

    # -- constructors --------------------------------------------------------
    @classmethod
    def from_dense(cls, w: jax.Array, cls_map: np.ndarray, tile: int,
                   fset: FormatSet = DEFAULT_FORMATS) -> "MPMatrix":
        cls_map = _check_codes(cls_map, fset)
        mt, nt = cls_map.shape
        wp = _pad_to(w.astype(jnp.float32), mt * tile, nt * tile)
        bufs = tile_buffers(wp, jnp.asarray(cls_map, jnp.int8), tile, fset)
        return cls(bufs, _HashableMap(cls_map), tile,
                   (w.shape[0], w.shape[1]), fset)

    def requantize(self, new_map: np.ndarray,
                   dense: jax.Array | None = None) -> "MPMatrix":
        """Re-quantize this matrix under a new class map (same tile grid /
        format set) — the precision-escalation primitive of the refinement
        solver (``repro.solve``).

        ``dense`` is the exact (pre-rounding) source values; promoting a
        tile then *recovers* the precision its old storage format dropped.
        Without ``dense`` the current storage-rounded values are re-tiled
        (promotion keeps the rounded values; demotion rounds further).
        """
        new_map = _check_codes(new_map, self.fset)
        if new_map.shape != self.cls.arr.shape:
            raise ValueError(
                f"new map {new_map.shape} != tile grid {self.cls.arr.shape}")
        src = self.to_dense() if dense is None else dense
        return MPMatrix.from_dense(src, new_map, self.tile, self.fset)

    # -- views ----------------------------------------------------------------
    def padded_dense(self) -> jax.Array:
        """Padded dense fp32 view with per-tile storage rounding applied
        (each tile is valid in exactly one buffer, the rest are zeros)."""
        return dense_of(self.bufs)

    def to_dense(self) -> jax.Array:
        """Materialize at fp32 with storage-precision rounding applied
        (this is the value every consumer sees after receiver-side convert)."""
        return self.padded_dense()[: self.shape[0], : self.shape[1]]

    @property
    def padded_shape(self) -> tuple[int, int]:
        return self.bufs[0].shape

    def storage_bytes(self) -> int:
        """Semantic storage bytes (what CompactMPMatrix would allocate)."""
        return P.map_storage_bytes(self.cls.arr, self.tile, self.fset)


# ---------------------------------------------------------------------------
# CompactMPMatrix — class-sorted compact tiles (the paper's memory model)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CompactMPMatrix:
    """Class-sorted tile storage: ``tiles[code]`` holds that format's tiles
    as ``storage_dtype[n_code, t, t]``.  ``slot[i,j]`` is the index of tile
    (i,j) inside its class array.  Allocated bytes == paper's storage."""

    tiles: tuple[jax.Array, ...]
    cls: _HashableMap      # int8[mt, nt] (static)
    slot: _HashableMap     # int32[mt, nt] (static)
    tile: int
    shape: tuple[int, int]
    fset: FormatSet = DEFAULT_FORMATS

    def tree_flatten(self):
        return (tuple(self.tiles),
                (self.cls, self.slot, self.tile, self.shape, self.fset))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(tuple(children), *aux)

    # -- role views (legacy accessors) --------------------------------------
    @property
    def tiles_hi(self) -> jax.Array:
        return self.tiles[self.fset.high]

    @property
    def tiles_lo(self) -> jax.Array:
        return self.tiles[self.fset.low]

    @property
    def tiles_lo8(self) -> jax.Array:
        if self.fset.low8 is None:
            return jnp.zeros((0, self.tile, self.tile), jnp.float8_e4m3fn)
        return self.tiles[self.fset.low8]

    @staticmethod
    def make_slots(cls_map: np.ndarray) -> np.ndarray:
        slot = np.zeros_like(cls_map, dtype=np.int32)
        for c in np.unique(cls_map):
            mask = cls_map == c
            slot[mask] = np.arange(mask.sum(), dtype=np.int32)
        return slot

    @classmethod
    def from_dense(cls, w: jax.Array, cls_map: np.ndarray, tile: int,
                   fset: FormatSet = DEFAULT_FORMATS) -> "CompactMPMatrix":
        cls_map = _check_codes(cls_map, fset)
        mt, nt = cls_map.shape
        m, n = mt * tile, nt * tile
        wp = _pad_to(w.astype(jnp.float32), m, n)
        tiles = wp.reshape(mt, tile, nt, tile).transpose(0, 2, 1, 3)
        tiles = tiles.reshape(mt * nt, tile, tile)
        slot = cls.make_slots(cls_map)
        flat_cls = cls_map.reshape(-1)

        def gather_class(code):
            fmt = fset.fmt(code)
            idx = np.nonzero(flat_cls == code)[0]
            if len(idx) == 0:
                return jnp.zeros((0, tile, tile), fmt.buffer_dtype)
            return fmt.to_buffer(tiles[jnp.asarray(idx)], tile=tile)

        return cls(tuple(gather_class(code) for code in fset.codes),
                   _HashableMap(cls_map), _HashableMap(slot), tile,
                   (w.shape[0], w.shape[1]), fset)

    def to_dense(self) -> jax.Array:
        mt, nt = self.cls.arr.shape
        t = self.tile
        out = jnp.zeros((mt * nt, t, t), jnp.float32)
        flat_cls = self.cls.arr.reshape(-1)
        flat_slot = self.slot.arr.reshape(-1)
        for code, buf in enumerate(self.tiles):
            idx = np.nonzero(flat_cls == code)[0]
            if len(idx) == 0:
                continue
            vals = buf[jnp.asarray(flat_slot[idx])].astype(jnp.float32)
            out = out.at[jnp.asarray(idx)].set(vals)
        dense = out.reshape(mt, nt, t, t).transpose(0, 2, 1, 3)
        dense = dense.reshape(mt * t, nt * t)
        return dense[: self.shape[0], : self.shape[1]]

    def to_mpmatrix(self) -> MPMatrix:
        dense = self.to_dense()
        return MPMatrix.from_dense(dense, self.cls.arr, self.tile, self.fset)

    def storage_bytes(self) -> int:
        return int(sum(buf.size * self.fset.bytes_of(code)
                       + buf.shape[0] * self.fset.meta_bytes_of(code)
                       for code, buf in enumerate(self.tiles)))


# ---------------------------------------------------------------------------
# KSplitWeight — structured-K production layout for LM matmuls
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KSplitWeight:
    """Weight W[K, N] whose precision map is constant along N within each
    K-block.  K-blocks are permuted so classes are contiguous:

        y = Σ_fmt  x[:, perm_fmt] @ w_fmt   (one dot per format, at that
                                             format's operational precision)

    Exact storage savings, exact HLO FLOPs (one dot per class, K split),
    trivially shardable along N (TP) — see DESIGN.md §3(3).

    ``k_cls`` int8[kt] is the per-K-block class code (static).  ``bufs``
    holds one ``[K_code, N]`` buffer per format code.  Gradient flows through
    all buffers (they are leaves).
    """

    bufs: tuple[jax.Array, ...]   # per format code: storage_dtype[K_code, N]
    k_cls: _HashableMap           # int8[kt]
    tile: int
    shape: tuple[int, int]        # logical (K, N)
    fset: FormatSet = DEFAULT_FORMATS

    def tree_flatten(self):
        return (tuple(self.bufs), (self.k_cls, self.tile, self.shape,
                                   self.fset))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(tuple(children), *aux)

    # -- role views (legacy accessors) --------------------------------------
    @property
    def w_hi(self) -> jax.Array:
        return self.bufs[self.fset.high]

    @property
    def w_lo(self) -> jax.Array:
        return self.bufs[self.fset.low]

    @property
    def w_lo8(self) -> jax.Array:
        if self.fset.low8 is None:
            return jnp.zeros((0, self.shape[1]), jnp.float8_e4m3fn)
        return self.bufs[self.fset.low8]

    # static helpers ---------------------------------------------------------
    @staticmethod
    def k_partition(k_cls: np.ndarray, tile: int,
                    fset: FormatSet = DEFAULT_FORMATS):
        """K-row indices per class in storage order (descending code, i.e.
        most-expensive format first — (hi, lo[, lo8]) for the default set)."""
        out = []
        for code in fset.class_order:
            blocks = np.nonzero(np.asarray(k_cls) == code)[0]
            rows = (blocks[:, None] * tile
                    + np.arange(tile)[None, :]).reshape(-1)
            out.append(rows.astype(np.int32))
        return tuple(out)

    @classmethod
    def from_dense(cls, w: jax.Array, k_cls: np.ndarray, tile: int,
                   fset: FormatSet = DEFAULT_FORMATS) -> "KSplitWeight":
        k_cls = _check_codes(np.asarray(k_cls, np.int8), fset)
        kt = k_cls.shape[0]
        k, n = w.shape
        if k != kt * tile:
            raise ValueError(
                f"K={k} must equal kt*tile={kt}*{tile} (choose a tile that "
                "divides K; padding K would desync with activations)")
        wp = w.astype(jnp.float32)
        parts = dict(zip(fset.class_order, cls.k_partition(k_cls, tile, fset)))
        bufs = []
        for code in fset.codes:
            idx = parts[code]
            rows = (wp[jnp.asarray(idx)] if len(idx)
                    else jnp.zeros((0, n), jnp.float32))
            bufs.append(fset.fmt(code).to_buffer(rows, tile=tile))
        return cls(tuple(bufs), _HashableMap(k_cls), tile, (k, n), fset)

    def to_dense(self) -> jax.Array:
        k, n = self.shape
        kt = self.k_cls.arr.shape[0]
        wp = jnp.zeros((kt * self.tile, n), jnp.float32)
        parts = self.k_partition(self.k_cls.arr, self.tile, self.fset)
        for code, idx in zip(self.fset.class_order, parts):
            if len(idx):
                wp = wp.at[jnp.asarray(idx)].set(
                    self.bufs[code].astype(jnp.float32))
        return wp[:k, :n]

    def storage_bytes(self) -> int:
        t = self.tile
        return int(sum(
            buf.size * self.fset.bytes_of(code)
            + (buf.shape[0] // t) * (-(-buf.shape[1] // t))
            * self.fset.meta_bytes_of(code)
            for code, buf in enumerate(self.bufs)))


# ---------------------------------------------------------------------------
# NSplitWeight — class map constant along K, split along N.  Used for
# row-parallel (TP-sharded-K) matmuls where K must stay contiguous but N is
# unsharded (DESIGN.md §5): y = concat([x32 @ w_hi, x16 @ w_lo], axis=-1).
# Class blocks are stored contiguously (most-expensive format's columns
# first); for data-driven policies the logical→stored column permutation is
# folded into the *next* layer's weights at init time (permutation folding —
# zero runtime cost).
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class NSplitWeight:
    bufs: tuple[jax.Array, ...]   # per format code: storage_dtype[K, N_code]
    n_cls: _HashableMap           # int8[nt] — class per N-block, STORED order
    tile: int
    shape: tuple[int, int]        # logical (K, N)
    fset: FormatSet = DEFAULT_FORMATS

    def tree_flatten(self):
        return (tuple(self.bufs), (self.n_cls, self.tile, self.shape,
                                   self.fset))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(tuple(children), *aux)

    @property
    def w_hi(self) -> jax.Array:
        return self.bufs[self.fset.high]

    @property
    def w_lo(self) -> jax.Array:
        return self.bufs[self.fset.low]

    @property
    def w_lo8(self) -> jax.Array:
        if self.fset.low8 is None:
            return jnp.zeros((self.shape[0], 0), jnp.float8_e4m3fn)
        return self.bufs[self.fset.low8]

    @classmethod
    def from_dense(cls, w: jax.Array, n_cls: np.ndarray, tile: int,
                   fset: FormatSet = DEFAULT_FORMATS) -> "NSplitWeight":
        """``n_cls`` must be class-sorted (descending code: the most
        expensive format's blocks first); the caller is responsible for any
        column permutation of ``w``."""
        n_cls = _check_codes(np.asarray(n_cls, np.int8), fset)
        k, n = w.shape
        if n != n_cls.shape[0] * tile:
            raise ValueError(f"N={n} != nt*tile={n_cls.shape[0]}*{tile}")
        order = np.argsort(-n_cls, kind="stable")  # descending code
        if not np.array_equal(order, np.arange(len(n_cls))):
            raise ValueError("n_cls must be class-sorted (fold permutations "
                             "into adjacent layers instead)")
        wp = w.astype(jnp.float32)
        cols = {code: int((n_cls == code).sum()) * tile
                for code in fset.codes}
        bufs = [None] * len(fset)
        start = 0
        for code in fset.class_order:
            stop = start + cols[code]
            bufs[code] = fset.fmt(code).to_buffer(wp[:, start:stop],
                                                  tile=tile)
            start = stop
        return cls(tuple(bufs), _HashableMap(n_cls), tile, (k, n), fset)

    def to_dense(self) -> jax.Array:
        return jnp.concatenate(
            [self.bufs[code].astype(jnp.float32)
             for code in self.fset.class_order], axis=1)

    def storage_bytes(self) -> int:
        t = self.tile
        return int(sum(
            buf.size * self.fset.bytes_of(code)
            + (-(-buf.shape[0] // t)) * (buf.shape[1] // t)
            * self.fset.meta_bytes_of(code)
            for code, buf in enumerate(self.bufs)))


#: reduce LOW-class row-parallel partial sums in the class's compute dtype
#: over the ICI — the class's reduction precision follows its storage
#: precision (receiver-side conversion extended to the TP collective;
#: EXPERIMENTS.md §Perf).  HIGH partials always reduce in fp32.
REDUCE_LOW_IN_BF16 = True


def nsplit_matmul(x: jax.Array, w: NSplitWeight) -> jax.Array:
    """y = x @ W, per-N-block operational precision, fp32 accumulation
    within a shard (the MXU accumulator); non-HIGH cross-shard reduction
    optionally in the class compute dtype (see REDUCE_LOW_IN_BF16)."""
    dims = (((x.ndim - 1,), (0,)), ((), ()))
    fset = w.fset
    parts = []
    for code in fset.class_order:
        buf = w.bufs[code]
        if not buf.shape[1]:
            continue
        fmt = fset.fmt(code)
        if code == fset.high:
            red_dt = jnp.float32
        else:
            red_dt = fmt.compute_dtype if REDUCE_LOW_IN_BF16 else jnp.float32
        parts.append(jax.lax.dot_general(
            x.astype(fmt.compute_dtype), buf.astype(fmt.compute_dtype), dims,
            precision=fmt.dot_precision,
            preferred_element_type=red_dt).astype(jnp.float32))
    return jnp.concatenate(parts, axis=-1) if len(parts) > 1 else parts[0]


def _take_k(x: jax.Array, idx: np.ndarray) -> jax.Array:
    """x[..., idx] — lowered as a slice when idx is contiguous (the balanced
    maps sort classes contiguously, so the common case is a free slice)."""
    if len(idx) and np.all(np.diff(idx) == 1):
        return jax.lax.slice_in_dim(x, int(idx[0]), int(idx[-1]) + 1, axis=-1)
    return jnp.take(x, jnp.asarray(idx), axis=-1)


def ksplit_matmul(x: jax.Array, w: KSplitWeight) -> jax.Array:
    """y = x @ W with receiver-side conversion per class.

    x: [..., K] (any float dtype).  Each class's slice of x is converted to
    that class's operational precision right before the dot (the TPU-register
    analogue of the paper's receiver-side conversion); accumulation fp32.
    """
    fset = w.fset
    parts_idx = w.k_partition(w.k_cls.arr, w.tile, fset)
    k, n = w.shape
    parts = []
    for code, idx in zip(fset.class_order, parts_idx):
        if not len(idx):
            continue
        fmt = fset.fmt(code)
        x_c = _take_k(x, idx).astype(fmt.compute_dtype)
        parts.append(jax.lax.dot_general(
            x_c, w.bufs[code].astype(fmt.compute_dtype),
            (((x.ndim - 1,), (0,)), ((), ())),
            precision=fmt.dot_precision,
            preferred_element_type=jnp.float32))
    if not parts:
        return jnp.zeros(x.shape[:-1] + (n,), jnp.float32)
    out = parts[0]
    for p in parts[1:]:
        out = out + p
    return out
