"""The main-path Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other test) cannot see what Mosaic refuses, so these
tests lower each kernel at real widths for a *described* v5e chip — the
TPU compiler is installed even where no chip is attached — and assert that
the compiled program holds the kernel (``tpu_custom_call``).  Decode
attention is compiled the same way, and its optimised HLO read for what
the serve steps must not do.  Nothing runs; a passing compile says nothing
about results or times.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and pytest-xdist workers
import every test module.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get, load_all
from repro.core.formats import FormatSet, format_set
from repro.core.layout import KSplitWeight, _HashableMap
from repro.core.linear import split_cls
from repro.kernels.convert import convert
from repro.kernels.grouped_gemm import _grouped_class_call
from repro.kernels.ksplit_gemm import ksplit_gemm_multi
from repro.kernels.mp_gemm_tile import format_specs, mp_gemm_tile_multi
from repro.kernels.split_gemm import split_gemm_tile_multi
from repro.models import common as MC
from repro.split.recovery import split_format_specs
from repro.tune import dispatch as TD
from repro.tune import search as TS
from repro.tune.costmodel import GemmPlan, GemmProblem, validate_plan
from repro.tune.device import DEVICE_TABLE

V5E = DEVICE_TABLE["tpu-v5e"]
N, T = 2048, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _lower_tile(sh, fset: FormatSet, n: int = N):
    def bufs():
        return tuple(_shape(sh, (n, n), f.buffer_dtype)
                     for f in fset.formats())
    maps = [_shape(sh, (n // T, n // T), jnp.int32)] * 3
    return mp_gemm_tile_multi.lower(bufs(), bufs(), bufs(), *maps, tile=T,
                                    specs=format_specs(fset),
                                    interpret=False)


def _lower_split(sh, fset: FormatSet, n: int = N):
    def bufs():
        return tuple(_shape(sh, (n, n), f.buffer_dtype)
                     for f in fset.formats())
    maps = [_shape(sh, (n // T, n // T), jnp.int32)] * 3
    return split_gemm_tile_multi.lower(bufs(), bufs(), bufs(), *maps,
                                       tile=T,
                                       specs=split_format_specs(fset),
                                       interpret=False)


def _lower_grouped(sh, fset: FormatSet, n: int = N):
    """One output class of the grouped kernel over an n² problem whose
    tiles split evenly over the set's formats."""
    nt = n // T
    per_fmt = nt * nt // len(fset)
    tiles = tuple(_shape(sh, (per_fmt + 1, T, T), f.buffer_dtype)
                  for f in fset.formats())
    slots = tuple(_shape(sh, (nt, nt), jnp.int32) for _ in fset.codes)
    coords = _shape(sh, (per_fmt,), jnp.int32)
    meta = (per_fmt, nt, format_specs(fset)[fset.high])
    return _grouped_class_call.lower(tiles, tiles, coords, coords, slots,
                                     slots, tile=T, interpret=False,
                                     meta=meta)


def _internlm2_mlp_weight(sh, fset: FormatSet | None = None):
    """Shapes of InternLM2-1.8B's MLP up-projection (d_model × d_ff) as a
    KSplitWeight under the config's own tile and precision policy."""
    load_all()
    cfg = get("internlm2-1.8b")
    fset = fset or FormatSet.from_key(cfg.mp_formats)
    k, n, t = cfg.d_model, cfg.d_ff, cfg.mp_tile
    k_cls = split_cls(k // t, cfg.mp_policy, fset=fset)
    rows = {c: int((k_cls == c).sum()) * t for c in fset.codes}
    bufs = tuple(_shape(sh, (rows[c], n), fset.fmt(c).buffer_dtype)
                 for c in fset.codes)
    return KSplitWeight(bufs, _HashableMap(k_cls), t, (k, n), fset)


def _lower_ksplit(sh, w: KSplitWeight, m: int, plan: GemmPlan):
    specs = format_specs(w.fset)
    order = w.fset.class_order
    x = _shape(sh, (m, w.shape[0]), jnp.bfloat16)
    return ksplit_gemm_multi.lower(
        x, tuple(w.bufs[c] for c in order),
        specs=tuple(specs[c] for c in order),
        bm=plan.bm, bn=plan.bn, bk=plan.bk, interpret=False)


def _assert_kernel(lowered):
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("fs", ["fp8_e4m3+bf16+fp32", "int8_pt+fp32"])
def test_tile_kernel_compiles(one_chip, fs):
    _assert_kernel(_lower_tile(one_chip, format_set(*fs.split("+"))))


def test_ksplit_kernel_compiles_at_internlm2_mlp_shape(one_chip):
    m = 256
    w = _internlm2_mlp_weight(one_chip)
    prob = TD.linear_problem(w, m)
    ranked = TS.rank_plans(
        TS.candidate_plans(prob, V5E, ("ksplit_pallas",)), prob, V5E)
    assert ranked, "the cost model admits no ksplit_pallas plan"
    _assert_kernel(_lower_ksplit(one_chip, w, m, ranked[0][0]))


@pytest.mark.parametrize("fs", ["fp8_e4m3+bf16+fp32", "int8_pt+fp32"])
def test_grouped_kernel_compiles(one_chip, fs):
    _assert_kernel(_lower_grouped(one_chip, format_set(*fs.split("+"))))


def test_split_kernel_compiles(one_chip):
    _assert_kernel(_lower_split(one_chip, format_set("bf16", "split3_e5m2")))


def test_convert_kernel_compiles(one_chip):
    x = _shape(one_chip, (N, N), jnp.float32)
    _assert_kernel(convert.lower(x, out_dtype=jnp.bfloat16,
                                 interpret=False))


_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>[\w.\-]+) = (?P<dtype>\w+)\[(?P<dims>[\d,]*)\]"
    r"\S* (?P<op>[\w\-]+)\((?P<args>[^)]*)\)(?P<attrs>.*)$", re.M)


@pytest.mark.parametrize("caller", ["decode_cont", "prefill_scan"])
def test_decode_attention_reads_the_gqa_cache_in_place(one_chip, caller):
    """At the InternLM2-1.8B backlog cell's shapes, neither the masked
    decode step (per-row slot and ``kv_valid``) nor the prefill scan's step
    (scalar position) materialises a copy of the cache repeated to every
    query head, and both contractions against the bf16 cache take their
    other operand (scaled q, probabilities) in f32 at HIGHEST."""
    B, S, nq, nkv, dh, d = 16, 512, 16, 8, 128, 2048
    dims = MC.AttnDims(nq, nkv, dh, nq, nkv)
    bf16 = lambda *shape: _shape(one_chip, shape, jnp.bfloat16)
    weights = {"wq": bf16(d, nq * dh), "wk": bf16(d, nkv * dh),
               "wv": bf16(d, nkv * dh), "wo": bf16(nq * dh, d)}
    cache = bf16(B, S, nkv, dh)
    if caller == "decode_cont":
        rows = _shape(one_chip, (B,), jnp.int32)
        step = dict(position=rows, slot=rows,
                    kv_valid=_shape(one_chip, (B, S), jnp.bool_))
    else:
        step = dict(position=_shape(one_chip, (), jnp.int32))

    def attend(w, x, ck, cv, step):
        params = {k: (lambda t, m=m: t @ m) for k, m in w.items()}
        return MC.decode_attention(params, x, dims, ck, cv, rope_theta=1e6,
                                   **step)

    hlo = jax.jit(attend).lower(weights, bf16(B, 1, d), cache, cache,
                                step).compile().as_text()
    instrs = list(_HLO_INSTR.finditer(hlo))
    shapes = {m["name"]: (m["dtype"], [int(n) for n in m["dims"].split(",")
                                       if n]) for m in instrs}
    repeated = [m["name"] for m in instrs
                if math.prod(shapes[m["name"]][1]) == B * S * nq * dh]
    assert not repeated, repeated
    cache_elems = B * S * nkv * dh
    against_cache = []
    for m in instrs:
        if m["op"] not in ("dot", "convolution"):
            continue
        ops = [shapes[a.strip().lstrip("%")] for a in m["args"].split(",")]
        if any(math.prod(dims) == cache_elems for _, dims in ops):
            against_cache.append(m)
            assert sorted(dt for dt, _ in ops) == ["bf16", "f32"], ops
            assert "operand_precision={highest,highest}" in m["attrs"]
    assert len(against_cache) == 2, [m["name"] for m in against_cache]


@pytest.mark.parametrize("path,fs", [
    ("tile", "fp8_e5m2+fp16+fp32"),
    ("grouped", "fp8_e5m2+fp16+fp32"),
    ("ksplit_pallas", "fp16+fp32"),
    ("split", "bf16+split2_fp16"),
])
def test_fp16_sets_refused_by_compiler_and_validator(one_chip, path, fs):
    """Mosaic on v5e cannot load, compute or slice in float16; the plan
    validator must keep these Pallas plans out on that device, while the
    XLA paths stay valid for the same set."""
    fset = format_set(*fs.split("+"))
    if path == "ksplit_pallas":
        w = _internlm2_mlp_weight(one_chip, fset)
        prob = TD.linear_problem(w, 256)

        def lower():
            return _lower_ksplit(one_chip, w, 256, GemmPlan(path))
    else:
        # uniform maps: LOW C tiles, or split-compound HIGH for the split
        # path (which needs a split C class to apply at all)
        code = fset.high if path == "split" else fset.low
        full = np.full((N // T, N // T), code, np.int8)
        prob = GemmProblem.from_maps(full, full, full, T, fset=fset)

        def lower():
            return {"tile": _lower_tile, "grouped": _lower_grouped,
                    "split": _lower_split}[path](one_chip, fset)
    # Pallas refuses the cast while lowering, Mosaic the load at compile
    with pytest.raises(Exception, match="(?i)mosaic|cast"):
        lower().compile()
    assert any("float16" in r
               for r in validate_plan(GemmPlan(path), prob, V5E))
    xla = "ksplit_xla" if path == "ksplit_pallas" else "ref"
    assert not validate_plan(GemmPlan(xla), prob, V5E)
