"""End-to-end behaviour tests for the paper's system: the tile-centric
mixed-precision GEMM as the matmul substrate of a small LM, trained on CPU,
checkpointed, restored, and served."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import load_all, reduced
from repro.core.precision import Policy
from repro.data.pipeline import make_batch
from repro.models import transformer as T
from repro.optim import adamw
from repro.train.train_step import make_train_step


def test_mp_policy_changes_storage_not_semantics():
    """Same seed, different policy ratio: losses start close (bf16 vs fp32
    storage noise only), storage bytes differ exactly 2x."""
    base = reduced(load_all()["llama3-8b"], tp=2)
    losses, bytes_ = {}, {}
    from repro.core.layout import KSplitWeight, NSplitWeight
    for ratio in (0.0, 1.0):
        cfg = dataclasses.replace(
            base, mp_policy=Policy(kind="ratio", ratio_high=ratio))
        params = T.init_model(jax.random.PRNGKey(0), cfg)
        batch = make_batch(cfg, 16, 2, kind="train", seed=1)
        loss, _ = jax.jit(lambda p, b, c=cfg: T.forward_train(p, c, b))(
            params, batch)
        losses[ratio] = float(loss)
        tot = 0
        for leaf in jax.tree.leaves(
                params, is_leaf=lambda x: isinstance(
                    x, (KSplitWeight, NSplitWeight))):
            if isinstance(leaf, (KSplitWeight, NSplitWeight)):
                tot += leaf.storage_bytes()
        bytes_[ratio] = tot
    assert abs(losses[0.0] - losses[1.0]) < 0.2, losses
    assert bytes_[0.0] * 2 == bytes_[1.0]


def test_norm_topk_policy_trains():
    cfg = dataclasses.replace(
        reduced(load_all()["internlm2-1.8b"], tp=2),
        mp_policy=Policy(kind="norm_topk", ratio_high=0.25))
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    ocfg = adamw.AdamWConfig(lr_peak=1e-3, warmup_steps=1, total_steps=10)
    opt = adamw.init(params, ocfg)
    step = jax.jit(make_train_step(cfg, ocfg, 1))
    batch = make_batch(cfg, 16, 2, kind="train")
    for _ in range(3):
        params, opt, m = step(params, opt, batch)
        assert bool(jnp.isfinite(m["loss"]))


def test_train_then_serve_roundtrip(tmp_path):
    """Train a few steps → checkpoint → restore → decode greedily."""
    from repro.checkpoint import ckpt
    from repro.serve import Engine, Request, ServeConfig
    cfg = reduced(load_all()["internlm2-1.8b"], tp=2)
    ocfg = adamw.AdamWConfig(lr_peak=1e-3, warmup_steps=1, total_steps=10)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    opt = adamw.init(params, ocfg)
    step = jax.jit(make_train_step(cfg, ocfg, 1))
    for s in range(3):
        params, opt, _ = step(params, opt,
                              make_batch(cfg, 16, 2, kind="train", step=s))
    ckpt.save(str(tmp_path / "ck"), {"params": params}, step=3)
    restored, _ = ckpt.restore(str(tmp_path / "ck"), {"params": params})
    eng = Engine(cfg, restored["params"],
                 ServeConfig(max_batch=1, max_seq=32))
    [req] = eng.generate([Request(np.array([1, 2, 3], np.int32),
                                  max_new_tokens=3)])
    assert len(req.out_tokens) == 3
    assert all(0 <= t < cfg.vocab for t in req.out_tokens)


def test_fp8_low8_class_end_to_end():
    """Beyond-paper LOW8 (fp8 e4m3) storage class: a model whose matmul
    weights carry a 25D:50S:25Q map trains with finite loss/grads, and
    storage accounting reflects the 1-byte class."""
    from repro.core.layout import KSplitWeight, NSplitWeight
    cfg = dataclasses.replace(
        reduced(load_all()["llama3-8b"], tp=2),
        mp_policy=Policy(kind="ratio", ratio_high=0.25, ratio_low8=0.25))
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    # fp8 buffers actually populated
    n_fp8 = sum(l.size for l in jax.tree.leaves(params)
                if hasattr(l, "dtype") and l.dtype == jnp.float8_e4m3fn)
    assert n_fp8 > 0
    ocfg = adamw.AdamWConfig(lr_peak=1e-3, warmup_steps=1, total_steps=10)
    opt = adamw.init(params, ocfg)
    step = jax.jit(make_train_step(cfg, ocfg, 1))
    batch = make_batch(cfg, 16, 2, kind="train")
    for _ in range(2):
        params, opt, m = step(params, opt, batch)
        assert bool(jnp.isfinite(m["loss"])), float(m["loss"])
    # storage: 25% fp32 + 50% bf16 + 25% fp8 ≈ 2.25 B/elem on split weights
    # (block-rounding makes small matrices deviate; check the effective rate)
    for leaf in jax.tree.leaves(params, is_leaf=lambda x: isinstance(
            x, (KSplitWeight, NSplitWeight))):
        if isinstance(leaf, (KSplitWeight, NSplitWeight)):
            elems = leaf.w_hi.size + leaf.w_lo.size + leaf.w_lo8.size
            rate = leaf.storage_bytes() / elems
            assert 2.0 <= rate <= 2.75, rate
            break
