"""Driver of the served decoder configurations: ``repro.serve.Engine`` at
the configuration's published widths, weights from the seed.

Set-up builds the model with ``repro.models.transformer.init_model`` (one
jitted call on the device), the engine with the configuration's
``ServeConfig``, and warms the configured buckets.  The window runs the
traffic file's loop, ``loops/<loop>.py``.

The check draws a sample of the finished requests (the longest among
them), runs the plain fp32 reference over each prompt and its served
tokens, and reads how far below the reference's best logit each served
token lies.
"""
from __future__ import annotations

import dataclasses
import gc

import numpy as np

from chipbench import gen
from chipbench.reference import internlm2 as ref


def _program_config(config: dict):
    from repro.configs import get, load_all
    load_all()
    cfg = dataclasses.replace(get(config["program_arch"]),
                              **config["program_overrides"])
    m = config["model"]
    have = {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "head_dim": cfg.head_dim or cfg.d_model // cfg.n_heads}
    prec = config["precision"]
    have_prec = {"formats": cfg.mp_formats, "tile": cfg.mp_tile,
                 "ratio_high": cfg.mp_policy.ratio_high,
                 "policy": cfg.mp_policy.kind}
    diff = {k: (v, m[k]) for k, v in have.items() if m[k] != v}
    diff.update({k: (v, prec[k]) for k, v in have_prec.items()
                 if prec[k] != v})
    if diff:
        raise ValueError(f"the program's {config['program_arch']} differs "
                         f"from the configuration file: {diff}")
    return cfg


def setup(spec: dict) -> dict:
    import jax

    from repro.models.transformer import init_model
    from repro.serve import Engine, ServeConfig

    config = spec["config"]
    cfg = _program_config(config)
    params = jax.jit(lambda k: init_model(k, cfg))(
        gen.jax_key(spec["seed"], 5))
    sc = config["serve"]
    engine = Engine(cfg, params, ServeConfig(
        max_batch=sc["max_batch"], max_seq=sc["max_seq"],
        buckets=tuple(sc["buckets"]),
        chunked_prefill=sc["chunked_prefill"]))
    engine.warmup()
    return {"engine": engine, "params": params, "spec": spec}


def collect(spec: dict, state: dict, record: dict) -> dict:
    """A sample of finished requests (the longest among them); the
    engine and its weights are freed."""
    done = state["done"]
    g = gen.rng(spec["seed"], 6)
    want = spec["config"]["check"]["requests"]
    longest = max(range(len(done)),
                  key=lambda i: len(done[i].prompt) + len(done[i].out_tokens))
    rest = [i for i in g.permutation(len(done)) if i != longest]
    picked = [longest] + rest[: want - 1]
    payload = {"requests": [(np.asarray(done[i].prompt, np.int32),
                             np.asarray(done[i].out_tokens, np.int32))
                            for i in picked]}
    state.clear()
    gc.collect()
    return payload


def gaps(spec: dict, requests: list, control: bool = False) -> np.ndarray:
    """For every served token, how far the reference's logit of it lies
    below the reference's best logit.  With ``control`` the token is the
    one the lower-precision control puts first at that position."""
    import jax.numpy as jnp

    config = spec["config"]
    key = gen.jax_key(spec["seed"], 5)
    T = config["serve"]["max_seq"]      # one shape for every sample
    toks = np.zeros((len(requests), T), np.int32)
    for i, (p, o) in enumerate(requests):
        seq = np.concatenate([p, o[:-1]])
        toks[i, : len(seq)] = seq
    lg = ref.logits(key, config["model"], config["precision"], toks)
    best = np.asarray(lg.max(-1))
    if control:
        low = ref.logits(key, config["model"], config["precision"], toks,
                         control=True)
        chosen = np.asarray(jnp.argmax(low, -1))
        del low
    out = []
    for i, (p, o) in enumerate(requests):
        pos = np.arange(len(p) - 1, len(p) + len(o) - 1)
        tok = chosen[i, pos] if control else o
        served = np.asarray(lg[i, jnp.asarray(pos), jnp.asarray(tok)])
        out.append(best[i, pos] - served)
    return np.concatenate(out)


def numbers(spec: dict, payload: dict, control: bool = False) -> dict:
    return {"max_logit_gap": float(gaps(spec, payload["requests"],
                                        control).max())}
