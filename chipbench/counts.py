"""Operations and bytes the benchmark's problems need, and the chip's peaks.

Everything here depends on the problem (shapes, tile maps, model widths),
never on the plan or path the program chose to run it, so a kernel's share
of its roofline reads the same work whatever implements it.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name: str) -> dict:
    with open(os.path.join(HERE, name)) as f:
        return json.load(f)


def peak_table() -> dict:
    """Device kind -> peak rates of one chip (Google Cloud documentation)."""
    return _load("peaks.json")["devices"]


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``; an unknown kind raises."""
    table = peak_table()
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"chipbench/peaks.json ({sorted(table)})")
    return dict(table[device_kind])


def formats() -> dict:
    """Format name -> storage/compute types, bytes and the peak it may use."""
    return _load("formats.json")["formats"]


# ---------------------------------------------------------------------------
# GEMM
# ---------------------------------------------------------------------------

def _tile_bytes(cls_names: np.ndarray, tile: int, fmts: dict) -> float:
    names, counts = np.unique(np.asarray(cls_names), return_counts=True)
    return float(sum(int(c) * (tile * tile * fmts[str(n)]["bytes"]
                               + fmts[str(n)]["scale_bytes_per_tile"])
                     for n, c in zip(names, counts)))


def gemm_least_time(pa: np.ndarray, pb: np.ndarray, pc: np.ndarray,
                    tile: int, device_kind: str, beta: float = 0.0) -> dict:
    """Least time of one ``C <- A·B + beta·C`` under per-tile format maps.

    ``pa``, ``pb``, ``pc`` are arrays of format names, one per tile.  The
    FLOP bound counts ``2·tile²·k`` per output tile at the fastest rate its
    class may use (integer formats at the int8 peak, every float format at
    the bf16 peak: the chip publishes no faster float rate).  The byte
    bound reads A and B once, writes the output once and reads C once when
    ``beta != 0``, each tile at its storage bytes plus its scale.
    """
    pk = peaks(device_kind)
    fmts = formats()
    pa, pb, pc = (np.asarray(p) for p in (pa, pb, pc))
    if pa.shape[1] != pb.shape[0] or pc.shape != (pa.shape[0], pb.shape[1]):
        raise ValueError(f"tile grids do not chain: {pa.shape} "
                         f"{pb.shape} {pc.shape}")
    k = pa.shape[1] * tile
    names, counts = np.unique(pc, return_counts=True)
    flop_s = 0.0
    flops = 0.0
    for n, c in zip(names, counts):
        f = 2.0 * tile * tile * k * int(c)
        flops += f
        flop_s += f / pk[fmts[str(n)]["peak"]]
    nbytes = (_tile_bytes(pa, tile, fmts) + _tile_bytes(pb, tile, fmts)
              + _tile_bytes(pc, tile, fmts) * (2 if beta else 1))
    byte_s = nbytes / pk["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes, "flop_s": flop_s,
            "byte_s": byte_s, "least_s": max(flop_s, byte_s),
            "bound": "flops" if flop_s >= byte_s else "bytes"}


# ---------------------------------------------------------------------------
# decoder-only transformer
# ---------------------------------------------------------------------------

def transformer_params(cfg: dict) -> dict:
    """Parameter counts of a dense GQA decoder with a gated MLP, from the
    configuration's published keys."""
    d = cfg["hidden_size"]
    nq = cfg["num_attention_heads"]
    nkv = cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or d // nq
    ff = cfg["intermediate_size"]
    per_layer = (d * nq * dh + 2 * d * nkv * dh + nq * dh * d
                 + 3 * d * ff + 2 * d)
    layers = cfg["num_hidden_layers"] * per_layer
    vocab = cfg["vocab_size"] * d
    return {"layers": layers, "embedding": vocab, "head": vocab,
            "total": layers + 2 * vocab + d}


def transformer_flops(cfg: dict, positions: int) -> float:
    """Model FLOPs to run ``positions`` consecutive tokens of one sequence
    from position 0: ``2·(layer parameters + head)`` per token, plus the
    attention scores and values over the context at each position."""
    p = transformer_params(cfg)
    nq = cfg["num_attention_heads"]
    dh = cfg.get("head_dim") or cfg["hidden_size"] // nq
    attn = 4.0 * cfg["num_hidden_layers"] * nq * dh
    t = int(positions)
    return 2.0 * (p["layers"] + p["head"]) * t + attn * t * (t + 1) / 2
