"""Training step: microbatched grad accumulation + AdamW + metrics.

``make_train_step(cfg, ocfg, microbatches)`` builds the pure function the
trainer jits.  Microbatches split the per-step batch along batch dim and
accumulate gradients in a bf16 accumulator with error feedback
(optim.grad_compress) — sequential scan, so peak activation memory is one
microbatch.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import transformer as T
from repro.optim import adamw
from repro.optim import grad_compress as GC


def loss_fn(params, cfg: ArchConfig, batch):
    loss, metrics = T.forward_train(params, cfg, batch)
    return loss, metrics


def make_train_step(cfg: ArchConfig, ocfg: adamw.AdamWConfig,
                    microbatches: int = 1, compress_accum: bool = True,
                    tune_params=None, tune_tokens: int | None = None):
    """``tune_params``: pass the (or a same-shaped) parameter tree to
    tune-once at setup — every MPLinear's GEMM plan is resolved against the
    per-microbatch token count *before* the step is jitted, so dispatch
    decisions are fixed and identical across recompilations."""
    from repro import obs
    if tune_params is not None:
        from repro.tune import dispatch as _tune
        with obs.span("train.tune_setup", "train",
                      m_hint=tune_tokens or 4096):
            _tune.warm_registry()
            _tune.tune_linear_params(tune_params,
                                     m_hint=tune_tokens or 4096)
    if obs.is_enabled():
        obs.event("train.step_config", "train", microbatches=microbatches,
                  compress_accum=compress_accum,
                  tuned=tune_params is not None)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            (loss, metrics), grads = grad_fn(params, cfg, batch)
        else:
            def split(x):
                b = x.shape[0]
                assert b % microbatches == 0, (b, microbatches)
                return x.reshape(microbatches, b // microbatches,
                                 *x.shape[1:])
            micro = jax.tree.map(split, batch)

            def micro_step(carry, mb):
                acc, err, loss_sum = carry
                (loss, _), grads = grad_fn(params, cfg, mb)
                if compress_accum:
                    acc, err = GC.accumulate(acc, grads, err)
                else:
                    acc = jax.tree.map(
                        lambda a, g: a + g.astype(a.dtype), acc, grads)
                return (acc, err, loss_sum + loss), None

            acc0 = jax.tree.map(
                lambda p: jnp.zeros(
                    p.shape, jnp.bfloat16 if compress_accum else jnp.float32),
                params)
            err0 = GC.ef_init(params) if compress_accum else acc0
            (acc, _, loss_sum), _ = jax.lax.scan(
                micro_step, (acc0, err0, jnp.zeros((), jnp.float32)),
                micro, length=microbatches)
            grads = jax.tree.map(
                lambda a: a.astype(jnp.float32) / microbatches, acc)
            loss = loss_sum / microbatches
            metrics = {"ce": loss, "aux": jnp.zeros(())}

        params, opt_state, opt_metrics = adamw.update(params, grads,
                                                      opt_state, ocfg)
        metrics = dict(metrics, **opt_metrics, loss=loss)
        return params, opt_state, metrics

    return train_step
