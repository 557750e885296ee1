"""Mixture-of-Experts FFN with tile-centric mixed-precision experts.

Dispatch: top-k token-choice routing with a fixed per-expert capacity and
gather/scatter index dispatch (no [T, E, C] one-hot tensors).  Expert
parallelism shards the E dim over "model" when E % axis == 0; otherwise
experts are replicated and each expert's d_ff is TP-sharded.

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


def _dispatch_tables(xf, router, top_k: int, capacity_factor: float):
    """Shared routing math: returns (table [E,C] token ids with sentinel T,
    gate_table [E,C], probs, flat_e, keep)."""
    T, d = xf.shape
    E = router.shape[1]
    logits = xf.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    C = max(int(np.ceil(T * top_k / E * capacity_factor)), 1)
    flat_e = expert_ids.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot
    my_pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], 1)[:, 0]
    keep = my_pos < C
    tok_idx = jnp.repeat(jnp.arange(T), top_k)
    table = jnp.full((E, C), T, jnp.int32)
    table = table.at[flat_e, jnp.where(keep, my_pos, C)].set(
        tok_idx, mode="drop")
    gate_table = jnp.zeros((E, C), jnp.float32)
    gate_table = gate_table.at[flat_e, jnp.where(keep, my_pos, C)].add(
        gate_vals.reshape(-1).astype(jnp.float32), mode="drop")
    return table, gate_table, probs, flat_e, keep, C


def moe_block_sharded(params, x, *, top_k: int, mesh, ep: bool,
                      capacity_factor: float = 1.25):
    """Explicit shard_map MoE — the collective-efficient production path.

    The pjit auto-sharded gather dispatch triggers "involuntary full
    rematerialization" in the SPMD partitioner (expert compute replicated
    over 'model', ~9× FLOPs and TB-scale all-reduces on qwen2 — see
    EXPERIMENTS.md §Perf iteration B).  Here the dataflow is explicit:

      * routing + dispatch tables are computed per data shard (tokens are
        data-sharded, x is replicated over 'model');
      * EP (E % tp == 0): every model shard gathers the [E, C, d] buckets
        locally (no communication — x is replicated over 'model') and
        computes only its own E/tp experts;
      * non-EP: every model shard computes all experts over its d_ff slice;
      * one bf16 psum over 'model' combines expert partial outputs.

    Returns (y [B,S,d], aux scalar).  Capacity is per-data-shard.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E = params["router"].shape[1]
    axes = mesh.axis_names
    data_axes = tuple(a for a in ("pod", "data") if a in axes)
    tp = mesh.shape["model"]

    gate, up, down = params["gate"], params["up"], params["down"]
    if ep:
        wspec = P("model", None, None)
        dspec = P("model", None, None)
    else:
        wspec = P(None, None, "model")          # gate/up: d_ff columns
        dspec = P(None, "model", None)          # down: d_ff rows
    dp = 1
    for a in data_axes:
        dp *= mesh.shape[a]
    if B % dp:                        # e.g. batch-1 long-context decode
        data_axes = ()
    da = (data_axes if len(data_axes) > 1 else
          (data_axes[0] if data_axes else None))
    # sequence-shard the activation over 'model' when S divides: the
    # boundary collectives become bf16 all-gather (in) / reduce-scatter
    # (out) and — critically — the backward cotangent of x is sharded
    # instead of an fp32 psum_invariant over 'model' (61 % of qwen2's
    # collective bytes before this change; EXPERIMENTS §Perf B3).
    seq_shard = S % tp == 0
    xspec = P(da, "model" if seq_shard else None, None)

    def local_fn(x_loc, router, g_hi, g_lo, u_hi, u_lo, d_hi, d_lo):
        if seq_shard:
            x_loc = jax.lax.all_gather(x_loc.astype(ACT_DTYPE), "model",
                                       axis=1, tiled=True)
        Bl, Sl, _ = x_loc.shape
        xf = x_loc.reshape(Bl * Sl, d)
        T = Bl * Sl
        table, gate_table, probs, flat_e, keep, C = _dispatch_tables(
            xf, router, top_k, capacity_factor)
        xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], 0)
        xe = jnp.take(xpad, table.reshape(-1), axis=0).reshape(E, C, d)
        if ep:
            e_loc = E // tp
            midx = jax.lax.axis_index("model")
            xe = jax.lax.dynamic_slice_in_dim(xe, midx * e_loc, e_loc, 0)
            gt = jax.lax.dynamic_slice_in_dim(gate_table, midx * e_loc,
                                              e_loc, 0)
            tbl = jax.lax.dynamic_slice_in_dim(table, midx * e_loc,
                                               e_loc, 0)
        else:
            gt, tbl = gate_table, table

        def mm(xin, hi, lo, prec_k_split=True):
            # per-class batched expert matmul (receiver-side conversion)
            parts = []
            if hi.shape[1 if prec_k_split else 2]:
                k_hi = hi.shape[1] if prec_k_split else None
                a = (xin[..., :hi.shape[1]] if prec_k_split else xin)
                parts.append(jnp.einsum(
                    "eck,ekn->ecn", a.astype(jnp.float32), hi,
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32))
            if lo.shape[1 if prec_k_split else 2]:
                a = (xin[..., hi.shape[1]:] if prec_k_split else xin)
                parts.append(jnp.einsum(
                    "eck,ekn->ecn", a.astype(jnp.bfloat16), lo,
                    preferred_element_type=jnp.float32))
            if len(parts) == 1:
                return parts[0]
            if prec_k_split:
                return parts[0] + parts[1]
            return jnp.concatenate(parts, -1)

        h = jax.nn.silu(mm(xe, g_hi, g_lo)) * mm(xe, u_hi, u_lo)
        h = h.astype(ACT_DTYPE)
        down_is_ksplit = ep
        ye = mm(h, d_hi, d_lo, prec_k_split=down_is_ksplit)
        weighted = (ye * gt[..., None]).astype(jnp.float32)
        out = jnp.zeros((T + 1, d), jnp.float32)
        out = out.at[tbl.reshape(-1)].add(
            weighted.reshape(-1, d), mode="drop")[:T]
        if seq_shard:
            out = out.reshape(Bl, Sl, d)
            out = jax.lax.psum_scatter(out.astype(jnp.bfloat16), "model",
                                       scatter_dimension=1, tiled=True)
            out = out.reshape(-1, d)
        else:
            out = jax.lax.psum(out.astype(jnp.bfloat16), "model")
        # load-balance aux (identical on every model shard)
        me = probs.mean(0)
        ce = jnp.zeros((E,), jnp.float32).at[flat_e].add(
            jnp.where(keep, 1.0, 0.0)) / max(T * top_k, 1)
        aux = E * jnp.sum(me * ce)
        for a in data_axes + ("model",):   # model-pmean: no-op numerically
            aux = jax.lax.pmean(aux, a)    # (satisfies vma replication)
        return out.reshape(Bl, -1, d), aux

    y, aux = shard_map(
        local_fn, mesh=mesh,
        in_specs=(xspec, P(), wspec, wspec, wspec, wspec, dspec, dspec),
        out_specs=(xspec, P()),
    )(x, params["router"], gate.w_hi, gate.w_lo, up.w_hi, up.w_lo,
      down.w_hi, down.w_lo)
    if "shared" in params:
        from repro.models.common import mlp_block
        y = (y.astype(jnp.float32)
             + mlp_block(params["shared"], x).astype(jnp.float32))
    return y.astype(ACT_DTYPE), aux


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
