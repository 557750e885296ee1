"""Driver of the GEMM configurations: ``repro.tune.mp_matmul`` on
tile-heterogeneous operands, looped for the window.

Set-up draws the tile maps and the scaled N(0, 1) operands from the seed, lays
the operands out as ``repro.core.MPMatrix`` and warms the call twice.  The
traffic's loop (``loops/<loop>.py``) drives ``mp_matmul(A, B, C)`` with no
plan, so the program's cost model picks the path.  The check compares a
sample of the last call's output, drawn from the seed and touching every
output tile, with the fp64 reference.
"""
from __future__ import annotations

import gc

import numpy as np

from chipbench import gen
from chipbench.reference import gemm as ref


def _maps(config: dict, traffic: dict, seed: int) -> dict:
    nt = config["n"] // config["tile"]
    return {op: gen.ratio_map((nt, nt), traffic["maps"][op],
                              config["roles"], gen.rng(seed, 2, i))
            for i, op in enumerate(("A", "B", "C"))}


def _dense(config: dict, seed: int):
    """A and B, N(0, 1) fp32 times the configuration's operand scale, made
    on the device in one call."""
    import jax
    import jax.numpy as jnp

    n, scale = config["n"], config["operand_scale"]

    @jax.jit
    def make(key):
        ka, kb = jax.random.split(key)
        return (jax.random.normal(ka, (n, n), jnp.float32) * scale,
                jax.random.normal(kb, (n, n), jnp.float32) * scale)

    return make(gen.jax_key(seed, 3))


def setup(spec: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core import MPMatrix
    from repro.core.formats import format_set
    from repro.tune import mp_matmul

    config, seed = spec["config"], spec["seed"]
    n, tile = config["n"], config["tile"]
    fset = format_set(*config["formats"])
    maps = _maps(config, spec["traffic"], seed)
    codes = {op: np.vectorize(fset.names.index)(m).astype(np.int8)
             for op, m in maps.items()}
    a, b = _dense(config, seed)
    A = MPMatrix.from_dense(a, codes["A"], tile, fset)
    B = MPMatrix.from_dense(b, codes["B"], tile, fset)
    C = MPMatrix.from_dense(jnp.zeros((n, n), jnp.float32), codes["C"],
                            tile, fset)
    del a, b
    for _ in range(2):
        jax.block_until_ready(mp_matmul(A, B, C))
    return {"A": A, "B": B, "C": C, "maps": maps, "call": mp_matmul,
            "tile": tile}


def _samples(spec: dict):
    config = spec["config"]
    tile, nt = config["tile"], config["n"] // config["tile"]
    per = config["check"]["per_tile"]
    g = gen.rng(spec["seed"], 4)
    return ref.sample(nt, tile, per, g), ref.sample(nt, tile, per, g)


def collect(spec: dict, state: dict, record: dict) -> dict:
    """The sampled output elements on the host; the program's arrays are
    freed."""
    import jax.numpy as jnp

    rows, cols = _samples(spec)
    ix = jnp.ix_(jnp.asarray(rows), jnp.asarray(cols))
    got = sum(buf[ix].astype(jnp.float32) for buf in state["out"].bufs)
    payload = {"rows": rows, "cols": cols, "maps": state["maps"],
               "got": np.asarray(got, np.float64)}
    state.clear()
    gc.collect()
    return payload


def _operands(spec: dict, rows, cols):
    """A's sampled rows, B's sampled columns and every tile's largest
    magnitude, drawn again from the seed on the device."""
    import jax.numpy as jnp

    n, tile = spec["config"]["n"], spec["config"]["tile"]
    a, b = _dense(spec["config"], spec["seed"])

    def amax(x):
        return np.asarray(jnp.abs(x).reshape(n // tile, tile, n // tile,
                                             tile).max(axis=(1, 3)))

    out = (np.asarray(a[jnp.asarray(rows)]), np.asarray(b[:, jnp.asarray(cols)]),
           amax(a), amax(b))
    del a, b
    return out


def numbers(spec: dict, payload: dict, control: bool = False) -> dict:
    """Per output format, the largest error of the sampled elements against
    the fp64 reference (as a share of the reference's rms).  With
    ``control`` the reference's own lower-precision twin stands in for the
    program's output."""
    tile = spec["config"]["tile"]
    maps, rows, cols = payload["maps"], payload["rows"], payload["cols"]
    if "operands" not in payload:
        payload["operands"] = _operands(spec, rows, cols)
    args = (*payload["operands"], maps["A"], maps["B"], maps["C"], tile,
            rows, cols)
    if "want" not in payload:
        payload["want"] = ref.reference(*args)
    got = ref.reference(*args, control=True) if control else payload["got"]
    return {f"max_err.{k}": v
            for k, v in ref.compare(got, payload["want"], maps["C"], tile,
                                    rows, cols).items()}
