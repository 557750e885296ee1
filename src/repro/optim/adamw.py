"""AdamW with fp32 master weights and mixed-precision parameter storage.

Model parameters live in the tile-heterogeneous layouts (bf16/fp32 split
buffers); the optimizer keeps fp32 master weights + moments and re-quantizes
into the storage layout after each update — the training-side counterpart of
the paper's storage-precision discipline.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr_peak: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    master_weights: bool = True
    # quantized optimizer state (beyond-paper): bf16 moments halve the
    # ZeRO-1 state footprint; updates still computed in fp32
    moment_dtype: str = "float32"


class AdamWState(NamedTuple):
    mu: Any
    nu: Any
    master: Any          # fp32 master copy (or None leaves)
    count: jax.Array


def lr_schedule(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    """Linear warmup → cosine decay to 10%."""
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    cos = 0.1 + 0.45 * (1 + jnp.cos(jnp.pi * prog))
    return cfg.lr_peak * warm * cos


def _is_decayable(path: tuple) -> bool:
    """Weight decay on matmul weights only (not norms/biases)."""
    names = "/".join(str(p) for p in path)
    return not any(s in names for s in ("norm", "b_", "bias", "b'"))


def init(params, cfg: AdamWConfig) -> AdamWState:
    mdt = jnp.dtype(cfg.moment_dtype)
    zeros = lambda p: jnp.zeros(p.shape, mdt)
    mu = jax.tree.map(zeros, params)
    nu = jax.tree.map(zeros, params)
    master = (jax.tree.map(lambda p: p.astype(jnp.float32), params)
              if cfg.master_weights else None)
    return AdamWState(mu, nu, master, jnp.zeros((), jnp.int32))


def global_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def update(params, grads, state: AdamWState, cfg: AdamWConfig):
    """Returns (new_params, new_state, metrics)."""
    count = state.count + 1
    lr = lr_schedule(cfg, count)
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))

    b1c = 1.0 - cfg.b1 ** count.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** count.astype(jnp.float32)

    src = state.master if cfg.master_weights else params

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_mu = treedef.flatten_up_to(state.mu)
    flat_nu = treedef.flatten_up_to(state.nu)
    flat_src = treedef.flatten_up_to(src)
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]]

    mdt = jnp.dtype(cfg.moment_dtype)
    new_p, new_mu, new_nu, new_master = [], [], [], []
    for p, g, mu, nu, m, path in zip(flat_p, flat_g, flat_mu, flat_nu,
                                     flat_src, paths):
        g32 = g.astype(jnp.float32) * scale
        mu32 = cfg.b1 * mu.astype(jnp.float32) + (1 - cfg.b1) * g32
        nu32 = (cfg.b2 * nu.astype(jnp.float32)
                + (1 - cfg.b2) * g32 * g32)
        upd = (mu32 / b1c) / (jnp.sqrt(nu32 / b2c) + cfg.eps)
        m32 = m.astype(jnp.float32)
        if _is_decayable(path):
            upd = upd + cfg.weight_decay * m32
        m_new = m32 - lr * upd
        new_p.append(m_new.astype(p.dtype))   # re-quantize into storage
        new_mu.append(mu32.astype(mdt))
        new_nu.append(nu32.astype(mdt))
        new_master.append(m_new)
    params_out = jax.tree.unflatten(treedef, new_p)
    state_out = AdamWState(
        jax.tree.unflatten(treedef, new_mu),
        jax.tree.unflatten(treedef, new_nu),
        jax.tree.unflatten(treedef, new_master) if cfg.master_weights
        else None,
        count)
    return params_out, state_out, {"lr": lr, "grad_norm": gnorm}
