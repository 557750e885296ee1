"""Per-architecture smoke tests: reduced config of the same family, one
forward/train step on CPU, asserting output shapes + no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import load_all, reduced
from repro.data.pipeline import make_batch
from repro.models import transformer as T

ARCHS = sorted(load_all().keys())


def _cfg(name):
    return reduced(load_all()[name], tp=2)


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = _cfg(arch)
    B, S = 2, 16
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg, S, B, kind="train", seed=0, step=0)
    loss, metrics = jax.jit(
        lambda p, b: T.forward_train(p, cfg, b))(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), (arch, float(loss))
    # a tiny model on random labels should start near ln(vocab)
    assert 0.5 * np.log(cfg.vocab) < float(loss) < 3 * np.log(cfg.vocab) + 5


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_shapes(arch):
    cfg = _cfg(arch)
    B, S = 2, 16
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg, S, B, kind="prefill", seed=0, step=0)
    logits = jax.jit(lambda p, b: T.forward_prefill(p, cfg, b))(params,
                                                                batch)
    assert logits.shape == (B, 1, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), arch


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if not load_all()[a].encoder_only])
def test_decode_steps(arch):
    cfg = _cfg(arch)
    B = 2
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    caches = T.init_cache(cfg, B, 32)
    dec = jax.jit(lambda p, t, c, pos: T.forward_decode(p, cfg, t, c, pos))
    tok = jnp.zeros((B, 1), jnp.int32)
    for pos in range(3):
        logits, caches = dec(params, tok, caches, jnp.int32(pos))
        assert logits.shape == (B, 1, cfg.vocab)
        assert bool(jnp.isfinite(logits).all()), (arch, pos)
        tok = logits.argmax(-1).astype(jnp.int32)


def test_encoder_only_has_no_decode():
    cfg = _cfg("hubert-xlarge")
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError):
        T.forward_decode(params, cfg, jnp.zeros((1, 1), jnp.int32),
                         T.init_cache(cfg, 1, 8), 0)


@pytest.mark.parametrize("arch", ["llama3-8b", "gemma3-4b", "xlstm-1.3b",
                                  "jamba-v0.1-52b"])
def test_decode_consistent_with_prefill(arch):
    """Teacher-forced decode over a prompt must agree with the bulk forward
    (validates every cache implementation end-to-end).  MoE archs run with
    a large capacity factor: capacity *drops* are batch-dependent by design
    (bulk may drop over-capacity tokens; single-token decode never does)."""
    import dataclasses
    cfg = _cfg(arch)
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    B, S = 1, 8
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    bulk = jax.jit(lambda p, b: T.forward_prefill(p, cfg, b))(
        params, {"tokens": toks})
    caches = T.init_cache(cfg, B, 16)
    dec = jax.jit(lambda p, t, c, pos: T.forward_decode(p, cfg, t, c, pos))
    logits = None
    for s in range(S):
        logits, caches = dec(params, toks[:, s:s + 1], caches, jnp.int32(s))
    np.testing.assert_allclose(
        np.asarray(logits, np.float32), np.asarray(bulk, np.float32),
        rtol=0.1, atol=0.15)  # bf16 path differences accumulate


def test_param_counts_match_published():
    reg = load_all()
    expect = {"llama3-8b": 8.0e9, "llama3-405b": 405.8e9,
              "jamba-v0.1-52b": 51.6e9, "phi3.5-moe-42b-a6.6b": 41.9e9,
              "qwen2-moe-a2.7b": 14.3e9, "llava-next-34b": 34.4e9}
    for name, want in expect.items():
        got = reg[name].param_count()
        assert abs(got - want) / want < 0.03, (name, got, want)
