"""Deterministic, shardable synthetic data pipeline.

Every batch is a pure function of ``(seed, step)`` so replays after a
restart/re-mesh are bit-identical regardless of the device grid — the
property the fault-tolerance story relies on (DESIGN.md §8).  A background
prefetch thread keeps ``depth`` batches ahead of the training loop.
"""
from __future__ import annotations

import queue
import threading
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig


def batch_spec(cfg: ArchConfig, seq_len: int, global_batch: int,
               kind: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input (the pipeline
    materializes matching arrays)."""
    B, S = global_batch, seq_len
    f32, i32 = jnp.float32, jnp.int32
    if kind in ("train", "prefill"):
        if cfg.frontend == "audio":
            spec = {"frames": jax.ShapeDtypeStruct((B, S, cfg.frontend_dim),
                                                   f32)}
        elif cfg.frontend == "vision":
            P = cfg.n_patches
            spec = {
                "patch_embeds": jax.ShapeDtypeStruct((B, P, cfg.frontend_dim),
                                                     f32),
                "tokens": jax.ShapeDtypeStruct((B, S - P), i32),
            }
        else:
            spec = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        if kind == "train":
            lab_s = S - cfg.n_patches if cfg.frontend == "vision" else S
            spec["labels"] = jax.ShapeDtypeStruct((B, lab_s), i32)
        return spec
    if kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((B, 1), i32)}
    raise ValueError(kind)


def make_batch(cfg: ArchConfig, seq_len: int, global_batch: int, *,
               kind: str = "train", seed: int = 0, step: int = 0) -> dict:
    """Materialize one deterministic batch matching ``batch_spec``."""
    rng = np.random.default_rng((seed << 20) ^ step)
    spec = batch_spec(cfg, seq_len, global_batch, kind)
    out = {}
    for name, s in spec.items():
        if s.dtype == jnp.int32:
            out[name] = jnp.asarray(
                rng.integers(0, cfg.vocab, size=s.shape, dtype=np.int32))
        else:
            out[name] = jnp.asarray(
                rng.standard_normal(s.shape, dtype=np.float32))
    return out


class Prefetcher:
    """Background-thread prefetch over ``make_batch`` keyed by step."""

    def __init__(self, cfg: ArchConfig, seq_len: int, global_batch: int, *,
                 kind: str = "train", seed: int = 0, start_step: int = 0,
                 depth: int = 2):
        self.cfg, self.seq, self.gb = cfg, seq_len, global_batch
        self.kind, self.seed = kind, seed
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._step = start_step
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _worker(self):
        step = self._step
        while not self._stop.is_set():
            b = make_batch(self.cfg, self.seq, self.gb, kind=self.kind,
                           seed=self.seed, step=step)
            while not self._stop.is_set():
                try:
                    self._q.put((step, b), timeout=0.1)
                    break
                except queue.Full:
                    continue
            step += 1

    def __iter__(self) -> Iterator[tuple[int, dict]]:
        while True:
            yield self._q.get()

    def close(self):
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=2)
