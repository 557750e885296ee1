"""Training loop: jitted step + prefetch + async checkpoint + watchdog.

The loop is restart-safe: on ``RestartSignal`` (straggler/failure, possibly
injected by tests) it restores the latest checkpoint — optionally onto a
shrunken mesh — and resumes from the saved step with the deterministic data
pipeline replaying the exact stream.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax

from repro.checkpoint import ckpt
from repro.configs.base import ArchConfig
from repro.data.pipeline import Prefetcher
from repro.models import transformer as T
from repro.optim import adamw
from repro.runtime.fault import Heartbeat, RestartSignal, Watchdog
from repro.train.train_step import make_train_step


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    seq_len: int = 128
    global_batch: int = 8
    microbatches: int = 1
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 50
    log_every: int = 10
    seed: int = 0
    heartbeat_path: str = ""
    fault_injector: Optional[Callable[[int], None]] = None  # tests


def train(cfg: ArchConfig, ocfg: adamw.AdamWConfig, tcfg: TrainerConfig,
          *, params=None, opt_state=None, start_step: int = 0,
          log: Callable[[str], None] = print, _history=None):
    """Returns (params, opt_state, history)."""
    if params is None:
        params = T.init_model(jax.random.PRNGKey(tcfg.seed), cfg)
    if opt_state is None:
        opt_state = adamw.init(params, ocfg)

    step_fn = jax.jit(make_train_step(
        cfg, ocfg, tcfg.microbatches, tune_params=params,
        tune_tokens=tcfg.seq_len * tcfg.global_batch // tcfg.microbatches))
    saver = ckpt.AsyncCheckpointer(tcfg.ckpt_dir)
    hb = Heartbeat(tcfg.heartbeat_path) if tcfg.heartbeat_path else None
    wd = Watchdog()
    history = _history if _history is not None else []

    pf = Prefetcher(cfg, tcfg.seq_len, tcfg.global_batch, kind="train",
                    seed=tcfg.seed, start_step=start_step)
    it = iter(pf)
    step = start_step
    try:
        while step < tcfg.steps:
            got_step, batch = next(it)
            assert got_step == step, (got_step, step)
            t0 = time.monotonic()
            try:
                if tcfg.fault_injector is not None:
                    tcfg.fault_injector(step)
                params, opt_state, metrics = step_fn(params, opt_state,
                                                     batch)
                loss = float(metrics["loss"])
            except RestartSignal as e:
                log(f"[fault] step {step}: {e.reason} → restore+resume")
                pf.close()
                return _recover(cfg, ocfg, tcfg, saver, e, params, opt_state,
                                step, log, history)
            dt = time.monotonic() - t0
            wd.record(dt)
            if hb:
                hb.beat(step, dt)
            fault = wd.check()
            if fault and "straggler" in fault:
                log(f"[watchdog] {fault}")
            if step % tcfg.log_every == 0:
                log(f"step {step:5d} loss {loss:.4f} "
                    f"gnorm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms")
            history.append({"step": step, "loss": loss, "time": dt})
            step += 1
            if step % tcfg.ckpt_every == 0 or step == tcfg.steps:
                saver.submit({"params": params, "opt": opt_state}, step)
    finally:
        pf.close()
    saver.wait()
    return params, opt_state, history


def _recover(cfg, ocfg, tcfg, saver, sig: RestartSignal, params, opt_state,
             step, log, history):
    """Restore from the newest checkpoint and resume (recursion-safe since
    the injector is consumed by clearing it for replayed steps)."""
    saver.wait()
    latest = saver.latest()
    if latest is None:
        log("[fault] no checkpoint yet → restart from step 0 state")
        restored = {"params": params, "opt": opt_state}
        resume_step = 0
    else:
        restored, manifest = ckpt.restore(latest,
                                          {"params": params,
                                           "opt": opt_state})
        resume_step = manifest["step"]
        log(f"[fault] restored step {resume_step} from {latest}")
    # clear the injector for steps already survived (prevents fault loops)
    inj = tcfg.fault_injector
    tcfg2 = dataclasses.replace(
        tcfg, fault_injector=(lambda s: None if s <= step else inj(s))
        if inj else None)
    # drop replayed history entries so the merged record is per-step unique
    kept = [h for h in history if h["step"] < resume_step]
    return train(cfg, ocfg, tcfg2, params=restored["params"],
                 opt_state=restored["opt"], start_step=resume_step, log=log,
                 _history=kept)
