"""Mixture-of-Experts FFN with tile-centric mixed-precision experts.

Dispatch: top-k token-choice routing with a fixed per-expert capacity and
gather/scatter index dispatch (no [T, E, C] one-hot tensors).
``init_moe(ep=...)`` lays the down projection out for expert parallelism
(per-expert K-split) or for a d_ff split (N-split).

Mixed precision at two granularities (DESIGN.md §5/§6):
  * per-expert K-split — every expert's weight carries the same K-class
    boundary (stackable, scannable);
  * expert-granular (beyond-paper) — the tile is the whole expert: E_hi
    experts run fp32, the rest bf16; counts balanced per shard.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.layout import _HashableMap
from repro.core.linear import choose_tile, split_cls
from repro.core.formats import DEFAULT_FORMATS
from repro.core.precision import Policy
from repro.models.common import ACT_DTYPE


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MoEKSplit:
    """Batched per-expert K-split weight: every expert shares the class
    boundary, so the buffers stack as [E, K_cls, N]."""

    w_hi: jax.Array   # f32[E, K_hi, N]
    w_lo: jax.Array   # bf16[E, K_lo, N]
    k_cls: _HashableMap
    tile: int
    shape: tuple[int, int, int]   # (E, K, N)

    def tree_flatten(self):
        return (self.w_hi, self.w_lo), (self.k_cls, self.tile, self.shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @classmethod
    def init(cls, key, e: int, k: int, n: int, policy: Policy | None,
             tile: int | None = None) -> "MoEKSplit":
        t = tile or choose_tile(k)
        kt = k // t
        if policy is None or policy.kind == "uniform_low":
            kcls = np.full(kt, DEFAULT_FORMATS.low, np.int8)
        else:
            kcls = split_cls(kt, policy)
        k_hi = int((kcls == DEFAULT_FORMATS.high).sum()) * t
        w = jax.random.normal(key, (e, k, n), jnp.float32) / np.sqrt(k)
        return cls(w[:, :k_hi, :],
                   w[:, k_hi:, :].astype(jnp.bfloat16),
                   _HashableMap(kcls), t, (e, k, n))

    def to_dense(self) -> jax.Array:
        return jnp.concatenate(
            [self.w_hi, self.w_lo.astype(jnp.float32)], axis=1)

    def storage_bytes(self) -> int:
        return self.w_hi.size * 4 + self.w_lo.size * 2

    def __call__(self, x: jax.Array) -> jax.Array:
        """x: [E, C, K] → [E, C, N], per-class operational precision."""
        k_hi = self.w_hi.shape[1]
        y = None
        if k_hi:
            y = jnp.einsum("eck,ekn->ecn", x[..., :k_hi].astype(jnp.float32),
                           self.w_hi, precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
        if self.w_lo.shape[1]:
            y_lo = jnp.einsum("eck,ekn->ecn",
                              x[..., k_hi:].astype(jnp.bfloat16), self.w_lo,
                              preferred_element_type=jnp.float32)
            y = y_lo if y is None else y + y_lo
        return y


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MoENSplit:
    """Batched per-expert N-split weight [E, K, N_cls] — used when K (d_ff)
    is TP-sharded so the class split must run along the unsharded N."""

    w_hi: jax.Array   # f32[E, K, N_hi]
    w_lo: jax.Array   # bf16[E, K, N_lo]
    n_cls: _HashableMap
    tile: int
    shape: tuple[int, int, int]

    def tree_flatten(self):
        return (self.w_hi, self.w_lo), (self.n_cls, self.tile, self.shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @classmethod
    def init(cls, key, e: int, k: int, n: int, policy: Policy | None,
             tile: int | None = None) -> "MoENSplit":
        t = tile or choose_tile(n)
        nt = n // t
        if policy is None or policy.kind == "uniform_low":
            ncls = np.full(nt, DEFAULT_FORMATS.low, np.int8)
        else:
            ncls = split_cls(nt, policy)
        n_hi = int((ncls == DEFAULT_FORMATS.high).sum()) * t
        w = jax.random.normal(key, (e, k, n), jnp.float32) / np.sqrt(k)
        return cls(w[:, :, :n_hi], w[:, :, n_hi:].astype(jnp.bfloat16),
                   _HashableMap(ncls), t, (e, k, n))

    def to_dense(self) -> jax.Array:
        return jnp.concatenate(
            [self.w_hi, self.w_lo.astype(jnp.float32)], axis=2)

    def storage_bytes(self) -> int:
        return self.w_hi.size * 4 + self.w_lo.size * 2

    def __call__(self, x: jax.Array) -> jax.Array:
        parts = []
        if self.w_hi.shape[2]:
            parts.append(jnp.einsum(
                "eck,ekn->ecn", x.astype(jnp.float32), self.w_hi,
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32))
        if self.w_lo.shape[2]:
            parts.append(jnp.einsum(
                "eck,ekn->ecn", x.astype(jnp.bfloat16), self.w_lo,
                preferred_element_type=jnp.float32))
        return jnp.concatenate(parts, -1) if len(parts) > 1 else parts[0]


def init_moe(key, d_model: int, d_ff: int, n_experts: int, top_k: int,
             policy: Policy | None, *, n_shared: int = 0,
             shared_d_ff: int | None = None, tile: int | None = None,
             ep: bool = True) -> dict:
    """``ep=True``: experts sharded over "model" → per-expert K-split down.
    ``ep=False``: d_ff sharded → N-split down (class along d_model out)."""
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    down_cls = MoEKSplit if ep else MoENSplit
    params = {
        "router": (jax.random.normal(kr, (d_model, n_experts), jnp.float32)
                   * 0.02),
        "gate": MoEKSplit.init(kg, n_experts, d_model, d_ff, policy, tile),
        "up": MoEKSplit.init(ku, n_experts, d_model, d_ff, policy, tile),
        "down": down_cls.init(kd, n_experts, d_ff, d_model, policy, tile),
    }
    if n_shared:
        from repro.models.common import init_mlp
        params["shared"] = init_mlp(ks, d_model,
                                    shared_d_ff or d_ff * n_shared, policy,
                                    tile)
    return params


def moe_block(params, x, *, top_k: int, capacity_factor: float = 1.25,
              return_aux: bool = False):
    """x: [B, S, d] → [B, S, d].  Gather/scatter dispatch with fixed
    capacity; dropped tokens (over capacity) fall through via the residual
    (standard practice)."""
    B, S, d = x.shape
    E = params["router"].shape[1]
    T = B * S
    xf = x.reshape(T, d)

    logits = xf.astype(jnp.float32) @ params["router"]      # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, top_k)     # [T, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    C = int(np.ceil(T * top_k / E * capacity_factor))
    C = max(C, 1)

    # position of each (token, slot) within its expert's queue
    flat_e = expert_ids.reshape(-1)                          # [T*k]
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)     # [T*k, E]
    pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot)        # exclusive cumsum
    my_pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], 1)[:, 0]
    keep = my_pos < C

    # scatter token indices into [E, C] dispatch table
    tok_idx = jnp.repeat(jnp.arange(T), top_k)
    table = jnp.full((E, C), T, jnp.int32)  # T = sentinel → zero row
    # over-capacity entries write to column C, which mode="drop" discards
    table = table.at[flat_e, jnp.where(keep, my_pos, C)].set(
        tok_idx, mode="drop")
    xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], 0)
    xe = jnp.take(xpad, table.reshape(-1), axis=0).reshape(E, C, d)

    h = jax.nn.silu(params["gate"](xe)) * params["up"](xe)
    ye = params["down"](h.astype(ACT_DTYPE))                # [E, C, d] f32

    # combine: scatter-add expert outputs × gate value back to tokens
    gates_flat = gate_vals.reshape(-1).astype(jnp.float32)
    gate_table = jnp.zeros((E, C), jnp.float32)
    gate_table = gate_table.at[flat_e, jnp.where(keep, my_pos, C)].add(
        gates_flat, mode="drop")
    weighted = ye * gate_table[..., None]
    out = jnp.zeros((T + 1, d), jnp.float32)
    out = out.at[table.reshape(-1)].add(weighted.reshape(E * C, d),
                                        mode="drop")
    out = out[:T]

    if "shared" in params:
        from repro.models.common import mlp_block
        out = out + mlp_block(params["shared"], xf).astype(jnp.float32)

    out = out.reshape(B, S, d).astype(ACT_DTYPE)
    if return_aux:
        # Switch-style load-balance loss
        me = probs.mean(0)                                   # [E]
        ce = jnp.zeros((E,), jnp.float32).at[flat_e].add(
            jnp.where(keep, 1.0, 0.0)) / max(T * top_k, 1)
        aux = E * jnp.sum(me * ce)
        return out, aux
    return out
