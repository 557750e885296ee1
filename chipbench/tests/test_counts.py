"""Operation and byte counts, checked by hand, and the peaks table."""
import numpy as np
import pytest

from chipbench import counts

KIND = "TPU v5 lite"


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


def test_gemm_counts_by_hand():
    # 2x2 tiles of 128, k = 256; C: one fp32, one bf16, one fp8, one int8
    t = 128
    pa = np.array([["fp32", "bf16"], ["bf16", "bf16"]])
    pb = np.array([["bf16", "bf16"], ["fp8_e4m3", "fp32"]])
    pc = np.array([["fp32", "bf16"], ["fp8_e4m3", "int8_pt"]])
    got = counts.gemm_least_time(pa, pb, pc, t, KIND)
    tile_flops = 2 * t * t * 256
    assert got["flops"] == 4 * tile_flops
    assert got["flop_s"] == pytest.approx(3 * tile_flops / 197e12
                                          + tile_flops / 393e12)
    a_bytes = t * t * (4 + 2 + 2 + 2)
    b_bytes = t * t * (2 + 2 + 1 + 4)
    c_bytes = t * t * (4 + 2 + 1 + 1) + 4
    assert got["bytes"] == a_bytes + b_bytes + c_bytes
    assert got["byte_s"] == pytest.approx(got["bytes"] / 819e9)
    assert got["bound"] == "bytes"          # k = 256 is bandwidth-bound
    beta = counts.gemm_least_time(pa, pb, pc, t, KIND, beta=1.0)
    assert beta["bytes"] == got["bytes"] + c_bytes


def test_gemm_16k_is_flop_bound():
    nt = 128
    full = np.full((nt, nt), "fp32")
    got = counts.gemm_least_time(full, full, full, 128, KIND)
    assert got["bound"] == "flops"
    assert got["least_s"] == pytest.approx(2 * 16384 ** 3 / 197e12)


def test_least_time_is_the_same_for_every_plan():
    """The count reads the problem (maps, shapes), never the plan: the
    same maps in any path, block shape or order give the same least
    time."""
    g = np.random.default_rng(0)
    names = np.array(["fp32", "bf16", "fp8_e4m3"])
    pa, pb, pc = (names[g.integers(0, 3, (4, 4))] for _ in range(3))
    want = counts.gemm_least_time(pa, pb, pc, 128, KIND)
    from repro.core.formats import format_set
    from repro.tune.costmodel import PATHS
    fset = format_set("fp8_e4m3", "bf16", "fp32")
    assert len(PATHS) > 1 and len(fset.names) == 3
    for _ in PATHS:      # one reading per plan path: nothing plan-shaped
        assert counts.gemm_least_time(pa, pb, pc, 128, KIND) == want


def test_internlm2_flops_per_token_match_its_parameter_count():
    cfg = {"hidden_size": 2048, "num_hidden_layers": 24,
           "num_attention_heads": 16, "num_key_value_heads": 8,
           "head_dim": 128, "intermediate_size": 8192, "vocab_size": 92544}
    p = counts.transformer_params(cfg)
    # InternLM2-1.8B: 1.89 billion parameters, 2 x 189.5 M of them the
    # untied embedding and head
    assert p["total"] == pytest.approx(1.889e9, rel=2e-3)
    assert p["embedding"] == p["head"] == 92544 * 2048
    one = counts.transformer_flops(cfg, 1)
    assert one == 2 * (p["total"] - p["embedding"] - 2048) + 4 * 24 * 2048
    # attention over the context grows with the position
    two = counts.transformer_flops(cfg, 2)
    assert two - one == one + 4 * 24 * 2048
