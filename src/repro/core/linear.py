"""MPLinear — the paper's tile-centric mixed-precision GEMM as an LM layer.

Every large matmul in the model stack goes through here.  The weight is a
split-layout tile-heterogeneous matrix (DESIGN.md §3(3)):

* ``ksplit`` — class map varies along K (contraction), constant along N.
  Used for column-parallel matmuls (K unsharded).
* ``nsplit`` — class map varies along N (output), constant along K.
  Used for row-parallel matmuls (K TP-sharded, N unsharded).
* ``dense``  — uniform single-precision weight (bf16), the 0D:100S endpoint,
  also the fallback when a dim cannot be tiled.

Policies (core.precision.Policy) pick which tiles are HIGH; `ratio` policies
produce class-sorted maps (zero-overhead slices); data-driven policies
(norm_topk) produce general maps on the ksplit path.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import DEFAULT_FORMATS, FormatSet
from repro.core.layout import KSplitWeight, NSplitWeight, nsplit_matmul
from repro.core.precision import Policy, role_class_vector

_TILE_PREFS = (128, 64, 32, 16, 8, 4, 2, 1)


def choose_tile(dim: int, prefer: int = 128) -> int:
    if dim % prefer == 0:
        return prefer
    for t in _TILE_PREFS:
        if dim % t == 0:
            return t
    return 1


def split_cls(nblocks: int, policy: Policy,
              block_norms: np.ndarray | None = None,
              fset: FormatSet = DEFAULT_FORMATS) -> np.ndarray:
    """Per-block class vector.  Ratio policies are class-sorted (HIGH first);
    norm_topk marks the largest-norm blocks HIGH in place."""
    if policy.kind in ("uniform_high",):
        return np.full(nblocks, fset.high, np.int8)
    if policy.kind in ("uniform_low",):
        return np.full(nblocks, fset.low, np.int8)
    if policy.kind in ("uniform_low8",):
        if fset.low8 is None:
            raise ValueError(f"format set {fset.names} has no low8 role")
        return np.full(nblocks, fset.low8, np.int8)
    n_hi = int(round(policy.ratio_high * nblocks))
    n_lo8 = int(round(policy.ratio_low8 * nblocks))
    if n_lo8 and fset.low8 is None:
        raise ValueError(f"format set {fset.names} has no low8 role")
    n_lo = nblocks - n_hi - n_lo8
    assert n_lo >= 0, (policy, nblocks)
    if policy.kind == "ratio":
        return role_class_vector(n_hi, n_lo, n_lo8, fset)
    if policy.kind == "norm_topk":
        if block_norms is None:
            raise ValueError("norm_topk needs block norms")
        cls = np.full(nblocks, fset.low, np.int8)
        order = np.argsort(-block_norms)
        cls[order[:n_hi]] = fset.high
        if n_lo8:
            cls[order[-n_lo8:]] = fset.low8
        return cls
    raise ValueError(f"unsupported policy kind {policy.kind!r}")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MPLinear:
    """y = x @ W (+ b).  ``w`` is one of KSplitWeight/NSplitWeight/plain
    bf16 array; ``b`` optional fp32."""

    w: object
    b: Optional[jax.Array]

    def tree_flatten(self):
        return (self.w, self.b), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __call__(self, x: jax.Array) -> jax.Array:
        if isinstance(self.w, KSplitWeight):
            # kernel/block choice comes from the tune dispatcher (registry/
            # cache resolved at trace time; falls back to the XLA ksplit
            # path on a miss).  Import lazily: tune sits above core.
            from repro.tune.dispatch import linear_matmul
            y = linear_matmul(x, self.w)
        elif isinstance(self.w, NSplitWeight):
            y = nsplit_matmul(x, self.w)
        else:
            y = jax.lax.dot_general(
                x.astype(self.w.dtype), self.w,
                (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        if self.b is not None:
            y = y + self.b
        return y

    @property
    def shape(self):
        if isinstance(self.w, (KSplitWeight, NSplitWeight)):
            return self.w.shape
        return self.w.shape

    def storage_bytes(self) -> int:
        if isinstance(self.w, (KSplitWeight, NSplitWeight)):
            return self.w.storage_bytes()
        return self.w.size * self.w.dtype.itemsize


def init_mp_linear(key: jax.Array, in_dim: int, out_dim: int,
                   policy: Policy | None, *, split: str = "ksplit",
                   tile: int | None = None, use_bias: bool = False,
                   scale: float | None = None,
                   fset: FormatSet = DEFAULT_FORMATS) -> MPLinear:
    """Initialize an MPLinear.  ``split`` ∈ {ksplit, nsplit, dense}.

    ``policy=None`` or split='dense' → plain low-format weight (the pure-LOW
    endpoint, no tile machinery — used as the memory-optimal default for
    matrices the policy does not cover).  ``fset`` picks which registered
    formats play the D/S/Q roles.
    """
    scale = scale if scale is not None else 1.0 / np.sqrt(in_dim)
    w = jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale
    b = jnp.zeros((out_dim,), jnp.float32) if use_bias else None
    if policy is None or split == "dense" or policy.kind == "uniform_low":
        return MPLinear(w.astype(fset.storage_dtype(fset.low)), b)
    if split == "ksplit":
        t = tile or choose_tile(in_dim)
        kt = in_dim // t
        norms = None
        if policy.kind == "norm_topk":
            norms = np.asarray(jnp.linalg.norm(
                w.reshape(kt, t, out_dim), axis=(1, 2)))
        cls = split_cls(kt, policy, norms, fset)
        return MPLinear(KSplitWeight.from_dense(w, cls, t, fset), b)
    if split == "nsplit":
        t = tile or choose_tile(out_dim)
        nt = out_dim // t
        if policy.kind == "norm_topk":
            # sort columns by norm, fold the permutation into storage order.
            norms = np.asarray(jnp.linalg.norm(
                w.reshape(in_dim, nt, t), axis=(0, 2)))
            cls = split_cls(nt, policy, norms, fset)
            order = np.argsort(-cls, kind="stable")
            colperm = (order[:, None] * t + np.arange(t)[None, :]).reshape(-1)
            w = w[:, jnp.asarray(colperm)]
            cls = cls[order]
        else:
            cls = split_cls(nt, policy, fset=fset)
        return MPLinear(NSplitWeight.from_dense(w, cls, t, fset), b)
    raise ValueError(f"unknown split {split!r}")


def mp_linear_flops(m_tokens: int, lin: MPLinear,
                    device_kind: str = "tpu-v5e") -> dict:
    """Model + MXU-weighted FLOPs for one application over m_tokens rows."""
    k, n = lin.shape
    base = 2 * m_tokens * k * n
    if isinstance(lin.w, (KSplitWeight, NSplitWeight)):
        fset = lin.w.fset
        cls = (lin.w.k_cls.arr if isinstance(lin.w, KSplitWeight)
               else lin.w.n_cls.arr)
    else:
        fset = DEFAULT_FORMATS
        cls = np.full(1, fset.low, np.int8)
    wts = np.array([fset.fmt(int(c)).cost_on(device_kind) for c in cls])
    return {"model_flops": base, "mxu_flops": base * float(wts.mean())}
