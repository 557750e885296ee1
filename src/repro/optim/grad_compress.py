"""Gradient compression with error feedback.

Two uses (DESIGN.md §8):

1. **Microbatch accumulation** — the gradient accumulator across microbatches
   is stored bf16 with an fp32 error-feedback residual, halving accumulator
   HBM while keeping the accumulated sum unbiased.
2. **Cross-pod hierarchical all-reduce** — within a pod the backward pass
   reduce-scatters in native precision; across pods gradients are cast bf16
   (error feedback applied locally) before the "pod"-axis psum, halving the
   slow inter-pod DCI/ICI traffic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map


def ef_init(tree):
    """fp32 error-feedback residuals, zeros like the gradient tree."""
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), tree)


def compress(grads, err):
    """(grads, err) → (bf16 grads, new err).  g_c = bf16(g + e);
    e' = (g + e) - g_c."""
    def one(g, e):
        g32 = g.astype(jnp.float32) + e
        gc = g32.astype(jnp.bfloat16)
        return gc, g32 - gc.astype(jnp.float32)
    flat = jax.tree.map(one, grads, err)
    gc = jax.tree.map(lambda t: t[0], flat,
                      is_leaf=lambda t: isinstance(t, tuple))
    e2 = jax.tree.map(lambda t: t[1], flat,
                      is_leaf=lambda t: isinstance(t, tuple))
    return gc, e2


def accumulate(acc, grads, err):
    """Add ``grads`` into a bf16 accumulator with error feedback.
    (All casts explicit — fp8-param cotangents arrive as fp8.)"""
    def one(a, g, e):
        s = a.astype(jnp.float32) + g.astype(jnp.float32) + e
        a2 = s.astype(jnp.bfloat16)
        return a2, s - a2.astype(jnp.float32)
    flat = jax.tree.map(one, acc, grads, err)
    a2 = jax.tree.map(lambda t: t[0], flat,
                      is_leaf=lambda t: isinstance(t, tuple))
    e2 = jax.tree.map(lambda t: t[1], flat,
                      is_leaf=lambda t: isinstance(t, tuple))
    return a2, e2


def cross_pod_mean(grads, err, mesh, axis: str = "pod"):
    """Hierarchical DP: mean the (already pod-locally-reduced) gradients
    across pods in bf16 with error feedback.  Specs: grads replicated within
    the scope of their existing sharding; only the '{axis}' dim
    participates."""
    npods = mesh.shape[axis]
    gc, err = compress(grads, err)

    def mean_fn(g):
        return jax.tree.map(
            lambda x: (jax.lax.psum(x.astype(jnp.float32), axis)
                       / npods).astype(jnp.bfloat16), g)

    from jax.sharding import PartitionSpec as P
    gc = shard_map(mean_fn, mesh=mesh,
                   in_specs=jax.tree.map(lambda _: P(), gc),
                   out_specs=jax.tree.map(lambda _: P(), gc))(gc)
    return gc, err
