"""The one generator of the benchmark's inputs, driven by a traffic file.

Every draw comes from ``--seed``.  A seed changes which values are drawn,
never how much work there is: tile maps have exact class counts, shuffled
by the seed; request lengths are a fixed set of quantiles of the traffic's
distribution, paired and ordered by the traffic file's own ``order_seed``,
and the seed draws the token ids.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """Host generator for ``seed`` (any non-negative integer) and a stream."""
    return np.random.default_rng([int(seed), *map(int, stream)])


def jax_key(seed: int, *stream: int):
    """A JAX PRNG key derived from ``seed`` (wider than 32 bits is fine)."""
    import jax
    word = np.random.SeedSequence([int(seed), *map(int, stream)]) \
        .generate_state(1, np.uint32)[0]
    return jax.random.PRNGKey(int(word))


def ratio_map(tiles: tuple[int, int], shares: dict, names: dict,
              g: np.random.Generator) -> np.ndarray:
    """Per-tile format names with exact role counts, shuffled by ``g``.

    ``shares`` maps a role (D, S, Q) to its fraction; S takes the rest.
    ``names`` maps each role to a format name.
    """
    n = tiles[0] * tiles[1]
    hi = int(round(shares.get("D", 0.0) * n))
    lo8 = int(round(shares.get("Q", 0.0) * n))
    if hi + lo8 > n:
        raise ValueError(f"role shares {shares} exceed 1")
    flat = np.array([names["D"]] * hi + [names["Q"]] * lo8
                    + [names["S"]] * (n - hi - lo8))
    g.shuffle(flat)
    return flat.reshape(tiles)


def lognormal_set(spec: dict, count: int) -> np.ndarray:
    """``count`` lengths at the mid-quantiles of a clipped lognormal with the
    spec's ``median``, ``sigma``, ``min`` and ``max``."""
    nd = NormalDist()
    qs = [(i + 0.5) / count for i in range(count)]
    vals = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(q))
            for q in qs]
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def request_set(traffic: dict, vocab: int, seed: int,
                round_index: int = 0) -> list:
    """One round of requests: ``(prompt ids, max_new_tokens)`` pairs.

    Prompt and output lengths are the fixed quantile sets of the traffic
    file, paired and ordered by its ``order_seed`` (the same in every
    round and for every seed: which requests share a batch, and when rows
    retire, is part of the work); token ids are uniform over the
    vocabulary, drawn from the seed."""
    count = traffic["round_requests"]
    order = rng(traffic["order_seed"], 7)
    prompts = lognormal_set(traffic["prompt"], count)
    outputs = lognormal_set(traffic["output"], count)
    order.shuffle(prompts)
    order.shuffle(outputs)
    g = rng(seed, 1, round_index)
    return [(g.integers(0, vocab, int(p)).astype(np.int32), int(o))
            for p, o in zip(prompts, outputs)]
