"""Shared model components: norms, RoPE, attention (flash-chunked, sliding
window, decode), MLP, embeddings, loss.

Every large matmul routes through ``core.linear.MPLinear`` — the paper's
tile-centric mixed-precision GEMM is the matmul layer of the whole stack.

Sharding conventions (see DESIGN.md §5): activations [batch → "data",
features replicated]; attention q-heads sharded over "model" (padded to a
multiple of the axis size when needed); KV heads duplicated up to the axis
size; MLP column-parallel then row-parallel; vocab sharded over "model".
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import DEFAULT_FORMATS, FormatSet
from repro.core.linear import init_mp_linear
from repro.core.precision import Policy

ACT_DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# small layers
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(ACT_DTYPE)


def init_rms_norm(d: int) -> jax.Array:
    return jnp.zeros((d,), jnp.float32)


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0
         ) -> jax.Array:
    """Rotary embedding.  x: [..., S, H, dh], positions: [..., S]."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    # [..., S, half]
    angles = positions[..., :, None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnDims:
    """Post-padding attention geometry.

    q heads are padded up to a multiple of the model-axis size; kv heads are
    duplicated up to the same count ratio so every shard owns matching q/kv
    head groups (standard Megatron GQA-TP).
    """
    n_q: int          # padded q heads
    n_kv: int         # duplicated kv heads (== n_q // group)
    head_dim: int
    n_q_orig: int
    n_kv_orig: int

    @property
    def group(self) -> int:
        return self.n_q // self.n_kv


def attn_dims(n_heads: int, n_kv_heads: int, d_model: int,
              model_axis: int, head_dim: int | None = None,
              kv_dup_to_tp: bool = False) -> AttnDims:
    dh = head_dim or d_model // n_heads
    nq = n_heads
    if nq % model_axis:                       # pad q heads for TP
        nq = int(np.ceil(nq / model_axis) * model_axis)
    group_orig = max(1, n_heads // n_kv_heads)
    # group must divide the padded q-head count; keep it ≤ the original
    # ratio so kv heads are only ever duplicated, never dropped
    candidates = [g for g in range(1, group_orig + 1) if nq % g == 0]
    if kv_dup_to_tp:
        # prefer groups whose kv-head count TP-shards: the KV cache then
        # splits over "model" (decode becomes memory-bound, not
        # collective-bound — EXPERIMENTS.md §Perf iteration A)
        sharded = [g for g in candidates if (nq // g) % model_axis == 0]
        if sharded:
            candidates = sharded
    group = max(candidates)
    nkv = nq // group
    return AttnDims(nq, nkv, dh, n_heads, n_kv_heads)


def init_attention(key, d_model: int, dims: AttnDims, policy: Policy | None,
                   tile: int | None = None,
                   fset: FormatSet = DEFAULT_FORMATS) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    nq, nkv, dh = dims.n_q, dims.n_kv, dims.head_dim
    return {
        # column-parallel (N sharded over model) → ksplit along K=d_model
        "wq": init_mp_linear(kq, d_model, nq * dh, policy, split="ksplit",
                             tile=tile, fset=fset),
        "wk": init_mp_linear(kk, d_model, nkv * dh, policy, split="ksplit",
                             tile=tile, fset=fset),
        "wv": init_mp_linear(kv, d_model, nkv * dh, policy, split="ksplit",
                             tile=tile, fset=fset),
        # row-parallel (K sharded over model) → nsplit along N=d_model
        "wo": init_mp_linear(ko, nq * dh, d_model, policy, split="nsplit",
                             tile=tile, fset=fset),
    }


def _qkv(params, x, dims: AttnDims, positions, rope_theta, use_rope=True):
    B, S, _ = x.shape
    nq, nkv, dh = dims.n_q, dims.n_kv, dims.head_dim
    q = params["wq"](x).reshape(B, S, nq, dh)
    k = params["wk"](x).reshape(B, S, nkv, dh)
    v = params["wv"](x).reshape(B, S, nkv, dh)
    if use_rope:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    return q.astype(ACT_DTYPE), k.astype(ACT_DTYPE), v.astype(ACT_DTYPE)


def _repeat_kv(k: jax.Array, group: int) -> jax.Array:
    """[B, S, n_kv, dh] → [B, S, n_q, dh]."""
    if group == 1:
        return k
    return jnp.repeat(k, group, axis=2)


def flash_attention(q, k, v, *, causal: bool, kv_chunk: int = 1024,
                    q_offset: int = 0) -> jax.Array:
    """Online-softmax chunked attention (memory O(S·kv_chunk) instead of
    O(S²)).  q: [B, H, Sq, dh], k/v: [B, H, Skv, dh]."""
    B, H, Sq, dh = q.shape
    Skv = k.shape[2]
    kv_chunk = min(kv_chunk, Skv)
    assert Skv % kv_chunk == 0, (Skv, kv_chunk)
    nchunks = Skv // kv_chunk
    scale = 1.0 / np.sqrt(dh)
    q32 = q.astype(jnp.float32) * scale

    kc = k.reshape(B, H, nchunks, kv_chunk, dh)
    vc = v.reshape(B, H, nchunks, kv_chunk, dh)
    q_pos = q_offset + jnp.arange(Sq)

    def step(carry, inputs):
        m, l, acc = carry
        idx, kb, vb = inputs
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, kb.astype(jnp.float32))
        if causal:
            kv_pos = idx * kv_chunk + jnp.arange(kv_chunk)
            mask = q_pos[:, None] >= kv_pos[None, :]
            s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.arange(nchunks), kc.transpose(2, 0, 1, 3, 4),
         vc.transpose(2, 0, 1, 3, 4)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(ACT_DTYPE)


def sliding_window_attention(q, k, v, *, window: int) -> jax.Array:
    """Banded causal attention with window ``w``: block i of queries attends
    to kv blocks (i-1, i) of width w — exact O(S·2w·dh) FLOPs in HLO.
    q, k, v: [B, H, S, dh]; S % window == 0."""
    B, H, S, dh = q.shape
    w = window
    if S <= w:
        return flash_attention(q, k, v, causal=True, kv_chunk=min(1024, S))
    assert S % w == 0, (S, w)
    nb = S // w
    scale = 1.0 / np.sqrt(dh)
    qb = q.reshape(B, H, nb, w, dh).astype(jnp.float32) * scale
    kb = k.reshape(B, H, nb, w, dh)
    vb = v.reshape(B, H, nb, w, dh)
    k_prev = jnp.concatenate([jnp.zeros_like(kb[:, :, :1]), kb[:, :, :-1]], 2)
    v_prev = jnp.concatenate([jnp.zeros_like(vb[:, :, :1]), vb[:, :, :-1]], 2)
    k_band = jnp.concatenate([k_prev, kb], 3)   # [B,H,nb,2w,dh]
    v_band = jnp.concatenate([v_prev, vb], 3)
    s = jnp.einsum("bhnqd,bhnkd->bhnqk", qb, k_band.astype(jnp.float32))
    # positions: query i (0..w-1 in block), key j (0..2w-1; j-w is same block)
    qi = jnp.arange(w)[:, None]
    kj = jnp.arange(2 * w)[None, :]
    valid = (kj - w <= qi) & (kj > qi - w)  # causal + window
    first_block = jnp.arange(nb)[:, None, None] == 0
    valid = valid[None, :, :] & (~first_block | (kj[None] >= w))
    s = jnp.where(valid[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhnqk,bhnkd->bhnqd", p, v_band.astype(jnp.float32))
    return out.reshape(B, H, S, dh).astype(ACT_DTYPE)


def attention_block(params, x, dims: AttnDims, *, positions, causal=True,
                    window: int | None = None, rope_theta=10000.0,
                    use_rope=True) -> jax.Array:
    """Full training/prefill attention.  x: [B, S, d]."""
    B, S, _ = x.shape
    q, k, v = _qkv(params, x, dims, positions, rope_theta, use_rope)
    q = q.transpose(0, 2, 1, 3)
    k = _repeat_kv(k, dims.group).transpose(0, 2, 1, 3)
    v = _repeat_kv(v, dims.group).transpose(0, 2, 1, 3)
    if window is not None and causal:
        out = sliding_window_attention(q, k, v, window=window)
    else:
        out = flash_attention(q, k, v, causal=causal,
                              kv_chunk=min(1024, S))
    out = out.transpose(0, 2, 1, 3).reshape(B, S, dims.n_q * dims.head_dim)
    return params["wo"](out).astype(ACT_DTYPE)


def decode_attention(params, x, dims: AttnDims, cache_k, cache_v, *,
                     position, rope_theta=10000.0, window: int | None = None,
                     use_rope: bool = True, slot: Optional[jax.Array] = None,
                     kv_valid: Optional[jax.Array] = None):
    """One-token decode.  x: [B, 1, d]; cache_k/v: [B, S_max, n_kv, dh]
    (possibly sequence-sharded — XLA inserts the two-pass softmax combine).
    Returns (out [B, 1, d], new_k, new_v).

    ``position`` may be per-request ([B] or [B, 1]) — it then feeds RoPE
    only, and the cache ``slot`` plus an explicit ``kv_valid`` [B, S_max]
    visibility mask must be supplied (the serve scheduler's right-padded
    microbatches: each request attends its own real prefix plus the
    generated suffix, never another request's padding).  ``slot`` itself
    may be a [B] vector — the continuous-decode engine's retire-and-refill
    slots progress independently per row, so each row scatters its new KV
    into its own cache position.

    GQA groups are contracted against the cache as stored: q (scaled, f32)
    is split into [B, 1, n_kv, group, dh], query head ``k·group + g``
    attending KV head ``k``, so no copy of the cache repeated to every
    query head is made.  Scores and ``p·V`` are f32 contractions at
    ``Precision.HIGHEST`` against the cache's values upcast (the cache
    itself stays in its dtype); the softmax is f32."""
    B = x.shape[0]
    nq, nkv, dh = dims.n_q, dims.n_kv, dims.head_dim
    S_max = cache_k.shape[1]
    batched_pos = jnp.ndim(position) != 0
    if batched_pos and (slot is None or kv_valid is None):
        raise ValueError("per-request position needs explicit slot+kv_valid")
    if kv_valid is not None and window is not None:
        raise ValueError("kv_valid masking is full-attention only")
    pos = position.reshape(B, 1) if batched_pos else jnp.full(
        (B, 1), position)
    q, k, v = _qkv(params, x, dims, pos, rope_theta, use_rope)
    if slot is None:
        slot = position
    if jnp.ndim(slot) != 0:
        if window is not None:
            raise ValueError("per-row slot vector is full-attention only")
        rows = jnp.arange(B)
        idx = jnp.reshape(slot, (B,))
        cache_k = cache_k.at[rows, idx].set(k[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[rows, idx].set(v[:, 0].astype(cache_v.dtype))
    else:
        slot = slot % S_max if window is not None else slot
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k.astype(cache_k.dtype), slot, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v.astype(cache_v.dtype), slot, axis=1)
    scale = 1.0 / np.sqrt(dh)
    qg = (q.astype(jnp.float32) * scale).reshape(B, 1, nkv, dims.group, dh)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, cache_k.astype(jnp.float32),
                   precision=hi)
    kv_pos = jnp.arange(S_max)
    if kv_valid is not None:
        valid = kv_valid
    elif window is not None:
        # in a ring buffer every slot is within the window once full
        filled = jnp.minimum(position + 1, S_max)
        valid = kv_pos[None, :] < filled
    else:
        valid = kv_pos[None, :] <= position
    if valid.ndim == 1:
        valid = valid[None]
    s = jnp.where(valid[:, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, cache_v.astype(jnp.float32),
                     precision=hi)
    out = out.reshape(B, 1, nq * dh).astype(ACT_DTYPE)
    return params["wo"](out).astype(ACT_DTYPE), cache_k, cache_v


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, policy: Policy | None,
             tile: int | None = None, gated: bool = True,
             fset: FormatSet = DEFAULT_FORMATS) -> dict:
    kg, ku, kd = jax.random.split(key, 3)
    p = {
        "up": init_mp_linear(ku, d_model, d_ff, policy, split="ksplit",
                             tile=tile, fset=fset),
        "down": init_mp_linear(kd, d_ff, d_model, policy, split="nsplit",
                               tile=tile, fset=fset),
    }
    if gated:
        p["gate"] = init_mp_linear(kg, d_model, d_ff, policy, split="ksplit",
                                   tile=tile, fset=fset)
    return p


def mlp_block(params, x) -> jax.Array:
    h = params["up"](x)
    if "gate" in params:
        h = jax.nn.silu(params["gate"](x)) * h
    else:
        h = jax.nn.gelu(h)
    return params["down"](h.astype(ACT_DTYPE)).astype(ACT_DTYPE)


# ---------------------------------------------------------------------------
# embedding / head / loss
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int) -> jax.Array:
    return (jax.random.normal(key, (vocab, d_model), jnp.float32)
            * 0.02).astype(ACT_DTYPE)


def embed(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0).astype(ACT_DTYPE)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  z_loss: float = 1e-4) -> jax.Array:
    """Mean CE over all positions; logits [.., V] (V may be model-sharded)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = (lse - ll).mean()
    if z_loss:
        loss = loss + z_loss * (lse ** 2).mean()
    return loss
