"""Plain fp32 ``jax.numpy`` forward of a dense GQA decoder (InternLM2).

The weights are drawn again from the seed, layer by layer, by the same
random draws the served model's initialiser makes (``jax.random`` keys
split in the same order, N(0, 1) scaled by ``1/sqrt(fan_in)``; embeddings
N(0, 1)·0.02), and rounded as the configuration stores them: in every
linear the first ``ratio_high`` of the tile blocks along the split axis
(K for q, k, v, gate, up and the head; N for o and down) are fp32, the
rest bf16; the embedding table is bf16.  Nothing is taken from the
program.  Matmuls run under ``jax.default_matmul_precision("highest")``;
activations stay fp32; attention is a plain causal softmax.

The control is the same forward with every stored tile one format lower
(fp32 tiles in bf16, bf16 tiles and the embedding in fp8 e4m3).

Departures from the published model: RMSNorm weights are ones (the
initialiser's value) and no dynamic RoPE scaling is applied (contexts here
stay far below the 32k positions it would start at).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: one format below each stored format, for the control
_BELOW = {jnp.float32: jnp.bfloat16, jnp.bfloat16: jnp.float8_e4m3fn}

#: linears split along K (axis 0) or N (axis 1)
_AXIS = {"wq": 0, "wk": 0, "wv": 0, "gate": 0, "up": 0, "wo": 1, "down": 1,
         "head": 0}


def _stored(w, dtype, control: bool):
    """``w`` in its storage type.  The rounding is returned as an array of
    that type, never converted back inside the same program: XLA may drop
    a narrowing convert that is followed by a widening one."""
    return w.astype(_BELOW[dtype] if control else dtype)


def _split_linear(key, fan_in: int, fan_out: int, axis: int, tile: int,
                  ratio_high: float, control: bool):
    """One linear as stored, ``(fp32 part, bf16 part)``: the first
    ``round(ratio_high · blocks)`` tile blocks along ``axis`` (0 = K,
    1 = N) fp32, the rest bf16."""
    w = jax.random.normal(key, (fan_in, fan_out), jnp.float32) \
        * (1.0 / np.sqrt(fan_in))
    blocks = w.shape[axis] // tile
    cut = int(round(ratio_high * blocks)) * tile
    hi, lo = jnp.split(w, [cut], axis=axis)
    return _stored(hi, jnp.float32, control), _stored(lo, jnp.bfloat16,
                                                      control)


def _dense(parts, axis: int):
    return jnp.concatenate([p.astype(jnp.float32) for p in parts], axis=axis)


def _dims(m: dict):
    d = m["hidden_size"]
    nq, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    dh = m.get("head_dim") or d // nq
    return d, nq, nkv, dh, m["intermediate_size"], m["vocab_size"]


def _keys(seed_key, n_layers: int):
    """The initialiser's key chain: (embedding, head, per-layer keys)."""
    keys = jax.random.split(seed_key, 8)
    lkey = keys[-1]
    layer_keys = []
    for _ in range(n_layers):
        lkey, sub = jax.random.split(lkey)
        layer_keys.append(sub)
    return keys[0], keys[1], layer_keys


@functools.partial(jax.jit, static_argnums=(1, 2))
def _layer_weights(key, m: tuple, control: bool):
    m = dict(m)
    d, nq, nkv, dh, ff, _ = _dims(m)
    tile, hi = m["tile"], m["ratio_high"]
    km, kf, _, _ = jax.random.split(key, 4)
    kq, kk, kv, ko = jax.random.split(km, 4)
    kg, ku, kd = jax.random.split(kf, 3)
    shapes = {"wq": (kq, d, nq * dh), "wk": (kk, d, nkv * dh),
              "wv": (kv, d, nkv * dh), "wo": (ko, nq * dh, d),
              "gate": (kg, d, ff), "up": (ku, d, ff), "down": (kd, ff, d)}
    return {name: _split_linear(k, fi, fo, _AXIS[name], tile, hi, control)
            for name, (k, fi, fo) in shapes.items()}


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, :, None].astype(jnp.float32) * freqs      # [B, T, half]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnums=(2,))
def _layer(x, parts, m: tuple):
    m = dict(m)
    d, nq, nkv, dh, _, _ = _dims(m)
    w = {name: _dense(p, _AXIS[name]) for name, p in parts.items()}
    with jax.default_matmul_precision("highest"):
        B, T, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        h = _rms(x, m["rms_norm_eps"])
        q = _rope((h @ w["wq"]).reshape(B, T, nq, dh), pos, m["rope_theta"])
        k = _rope((h @ w["wk"]).reshape(B, T, nkv, dh), pos,
                  m["rope_theta"])
        v = (h @ w["wv"]).reshape(B, T, nkv, dh)
        g = nq // nkv
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
        causal = jnp.tril(jnp.ones((T, T), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, nq * dh)
        x = x + o @ w["wo"]
        h = _rms(x, m["rms_norm_eps"])
        return x + (jax.nn.silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"]


@functools.partial(jax.jit, static_argnums=(1, 2))
def _embedding(key, vocab_d: tuple, control: bool):
    table = jax.random.normal(key, vocab_d, jnp.float32) * 0.02
    return _stored(table, jnp.bfloat16, control)


@jax.jit
def _lookup(table, tokens):
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _head_weights(key, m: tuple, control: bool):
    m = dict(m)
    d, _, _, _, _, vocab = _dims(m)
    return _split_linear(key, d, vocab, _AXIS["head"], m["tile"],
                         m["ratio_high"], control)


@functools.partial(jax.jit, static_argnums=(2,))
def _head(x, parts, m: tuple):
    m = dict(m)
    with jax.default_matmul_precision("highest"):
        return _rms(x, m["rms_norm_eps"]) @ _dense(parts, _AXIS["head"])


def logits(seed_key, model: dict, precision: dict, tokens: np.ndarray,
           control: bool = False):
    """fp32 logits ``[B, T, vocab]`` (on the device) for ``tokens`` ``[B, T]``
    (rows are independent causal sequences; padding sits after them)."""
    m = tuple(sorted({**model, "tile": precision["tile"],
                      "ratio_high": precision["ratio_high"]}.items()))
    k_embed, k_head, layer_keys = _keys(seed_key, model["num_hidden_layers"])
    table = _embedding(k_embed, (model["vocab_size"], model["hidden_size"]),
                       control)
    x = _lookup(table, jnp.asarray(tokens))
    del table
    for lk in layer_keys:
        x = _layer(x, _layer_weights(lk, m, control), m)
    return _head(x, _head_weights(k_head, m, control), m)
