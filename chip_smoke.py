#!/usr/bin/env python3
"""Bring-up check on one TPU: the tile-centric GEMM, the serve engine at
InternLM2-1.8B width and the refinement solver, each driven once through
the entry points a user calls.

    python chip_smoke.py               # one chip: phases gemm, serve, solve
    python chip_smoke.py --four-chips  # only: 2x2 SUMMA vs one-device GEMM

Every phase checks its own results (fp64 reference bounds, batched
tokens vs ``Engine.generate_reference``, the solver's exit gate and a
host-side fp64 check of its answer); the first failure exits
non-zero.  Without a TPU the script exits non-zero before any phase.  The
last line of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Weights and data are random, drawn from fixed seeds.  The plan cache is a
fresh file under ``chiprun_out/chip_smoke/``; the compile cache follows
``JAX_COMPILATION_CACHE_DIR`` or else ``<checkout>/.jax_cache``.  These
are bring-up runs: the wall times printed include compilation and are not
benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

#: (format set, map name, D share, Q share) of the gemm phase
GEMM_CASES = (
    ("fp8_e4m3+bf16+fp32", "100D0S", 1.0, 0.0),
    ("fp8_e4m3+bf16+fp32", "50D50S", 0.5, 0.0),
    ("fp8_e4m3+bf16+fp32", "0D80S20Q", 0.0, 0.2),
    ("int8_pt+fp32", "50D50S", 0.5, 0.0),
)
#: the case that also runs a measured autotune
AUTOTUNE_CASE = ("fp8_e4m3+bf16+fp32", "50D50S")


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# preflight
# ---------------------------------------------------------------------------

def preflight():
    """Refuse to run anywhere but on a TPU of a known kind, from a checkout.
    Returns the first device."""
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SmokeFailure(f"no src/repro next to {__file__}: run the "
                           "script from a checkout of the repository")
    sys.path.insert(0, src)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX's first device is {dev.platform} "
                           f"({dev.device_kind})")
    import repro
    from repro.tune.device import detect_device

    check(not repro.config.get("device"),
          "REPRO_TUNE_DEVICE is set: the device must be detected, not "
          "forced")
    spec = detect_device(dev)
    check(spec.kind == "tpu-v5e",
          f"device_kind {dev.device_kind!r} resolves to {spec.kind}, "
          "not tpu-v5e")
    cache_dir = repro.config.use_compile_cache()
    os.makedirs(OUT_DIR, exist_ok=True)
    plans = os.path.join(OUT_DIR, "plans.json")
    if os.path.exists(plans):
        os.remove(plans)
    repro.configure(tune_cache=plans, tune_cache_only=False)
    log(f"device_kind={dev.device_kind!r} count={len(jax.devices())} "
        f"jax={jax.__version__} spec={spec.kind}")
    log(f"compile cache: {cache_dir}")
    log(f"plan cache: {plans} (fresh)")
    return dev


# ---------------------------------------------------------------------------
# phase gemm: every valid plan of the core GEMM at 4096^3
# ---------------------------------------------------------------------------

def _operands(n: int, tile: int, fset, hi: float, q: float, seed: int):
    """Dense fp32 A, B (device) and MPMatrix A, B, zero C under ratio maps
    drawn from ``seed``."""
    import jax
    import jax.numpy as jnp

    from repro.core import MPMatrix
    from repro.core.precision import Policy, make_map

    maps = [make_map((n, n), tile,
                     Policy(kind="ratio", ratio_high=hi, ratio_low8=q,
                            seed=seed + i), fset=fset)
            for i in range(3)]
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(ka, (n, n), jnp.float32)
    b = jax.random.normal(kb, (n, n), jnp.float32)
    A = MPMatrix.from_dense(a, maps[0], tile, fset)
    B = MPMatrix.from_dense(b, maps[1], tile, fset)
    C = MPMatrix.from_dense(jnp.zeros((n, n), jnp.float32), maps[2], tile,
                            fset)
    return a, b, A, B, C, maps


def phase_gemm(n: int = 4096, tile: int = 128) -> None:
    import jax
    import numpy as np

    from repro.core.accuracy import check_against_fp64
    from repro.core.formats import format_set
    from repro.tune import dispatch as TD
    from repro.tune import search as TS
    from repro.tune.device import detect_device

    dev = detect_device()
    kernel_checked = False
    for seed, (fs, name, hi, q) in enumerate(GEMM_CASES):
        fset = format_set(*fs.split("+"))
        a, b, A, B, C, (pa, pb, pc) = _operands(n, tile, fset, hi, q, seed)
        a64, b64 = np.asarray(a), np.asarray(b)
        prob = TD.problem_of(A, B, C)
        plans = TS.candidate_plans(prob, dev)
        check(bool(plans), f"{fs} {name}: no valid plan on {dev.kind}")
        for plan in plans:
            t0 = time.perf_counter()
            out = jax.block_until_ready(TD.execute_plan(plan, A, B, C))
            dt = time.perf_counter() - t0
            rep = check_against_fp64(np.asarray(out.to_dense()), a64, b64,
                                     None, pa, pb, pc, tile, fset)
            worst = {fset.names[c]: round(v, 4)
                     for c, v in rep["worst_ratio"].items()}
            log(f"gemm {fs} {name} {plan.key():24s} ok={rep['ok']} "
                f"worst_ratio={worst} first_call_s={dt:.3f}")
            check(rep["ok"], f"{fs} {name} {plan.key()} outside its bound")
            if plan.path in ("tile", "grouped", "ksplit_pallas", "split") \
                    and not kernel_checked:
                text = jax.jit(
                    lambda A, B, C, plan=plan:
                    TD.execute_plan(plan, A, B, C).bufs
                ).lower(A, B, C).compile().as_text()
                check("tpu_custom_call" in text,
                      f"{plan.key()}: no tpu_custom_call in the compiled "
                      "program (interpret mode?)")
                kernel_checked = True
                log(f"gemm {plan.key()} compiled program holds "
                    "tpu_custom_call")
        if (fs, name) == AUTOTUNE_CASE:
            best, report = TS.autotune_problem(
                prob, lambda p: TD.execute_plan(p, A, B, C).bufs, dev=dev,
                force=True)
            check(report["source"] == "measured",
                  f"autotune did not measure: {report}")
            for row in report["candidates"]:
                log(f"autotune {row['plan']:24s} "
                    f"measured_us={row['measured_us']:.1f} "
                    f"predicted_us={row['predicted_us']:.1f}")
            log(f"autotune winner {best.key()}")
    check(kernel_checked, "no Pallas plan ran in the gemm phase")


# ---------------------------------------------------------------------------
# phase serve: InternLM2-1.8B at published width through the serve launcher
# ---------------------------------------------------------------------------

def phase_serve(arch: str = "internlm2-1.8b", n_requests: int = 8,
                min_len: int = 16, max_len: int = 200,
                buckets: str = "64,128,256", seed: int = 0) -> None:
    import jax
    import numpy as np

    from repro import obs
    from repro.configs import get, load_all
    from repro.launch import serve as launcher
    from repro.models.common import attn_dims
    from repro.serve import Request
    from repro.tune import dispatch as TD

    load_all()
    vocab = get(arch).vocab
    max_new = 16
    max_pad = max(int(b) for b in buckets.split(","))
    rng = np.random.default_rng(seed)
    lengths = rng.integers(min_len, max_len + 1, size=n_requests)
    obs.metrics_registry().reset(TD.DISPATCH_METRIC)
    args = launcher.parse_args(
        ["--arch", arch, "--max-new", str(max_new),
         "--max-batch", "4", "--max-seq", str(max_pad + max_new),
         "--buckets", buckets, "--seed", str(seed), "--prompts"]
        + [" ".join(str(int(v)) for v in rng.integers(0, vocab, n))
           for n in lengths])
    cfg, eng, reqs = launcher.run(args)
    check(cfg == dataclasses.replace(get(arch), tp=1),
          f"served config differs from the registered {arch} beyond tp=1")
    dims = attn_dims(cfg.n_heads, cfg.n_kv_heads, cfg.d_model, cfg.tp,
                     cfg.head_dim, cfg.kv_dup_to_tp)
    log(f"serve {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads} kv_heads={cfg.n_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab} formats={cfg.mp_formats} policy={cfg.mp_policy}")
    log(f"serve attn_dims={dims}")
    check(dims.n_kv == cfg.n_kv_heads, "KV heads duplicated on one chip")
    bad = [(i, r.error) for i, r in enumerate(reqs)
           if r.error or not r.done]
    check(not bad, f"requests not served: {bad}")
    st = eng.stats()
    recompiles = st["compile"]["post_warmup_recompiles"]
    log(f"serve served={st['requests']['served']} "
        f"post_warmup_recompiles={recompiles} "
        f"warmup_traces={st['compile']['warmup_traces']}")
    check(recompiles == 0, f"{recompiles} recompiles after warmup")
    paths = {}
    for labels, counter in obs.metrics_registry().series(
            TD.DISPATCH_METRIC):
        key = f"{labels['path']}/{labels['op']}"
        paths[key] = paths.get(key, 0) + int(counter.value)
    log(f"serve dispatch.calls by path (trace-time) {paths}")
    ref = eng.generate_reference(
        [Request(r.prompt, max_new_tokens=r.max_new_tokens,
                 temperature=r.temperature, fset=r.fset, seed=r.seed)
         for r in reqs])
    for i, (r, want) in enumerate(zip(reqs, ref)):
        if r.out_tokens != want.out_tokens:
            first = next(k for k, (x, y) in enumerate(
                zip(r.out_tokens, want.out_tokens)) if x != y)
            raise SmokeFailure(
                f"request {i} (prompt {len(r.prompt)} tokens): batched "
                f"tokens diverge from generate_reference at token {first}: "
                f"{r.out_tokens} vs {want.out_tokens}")
    log(f"serve batched == reference tokens for all {len(reqs)} requests")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"serve peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------
# phase solve: the refinement solver through its launcher
# ---------------------------------------------------------------------------

def phase_solve(n: int = 4096, tile: int = 128) -> None:
    import numpy as np

    from repro.core.accuracy import hpl_mxp_metric
    from repro.core.formats import DEFAULT_FORMATS
    from repro.launch import solve as launcher
    from repro.solve import graded_spd, rhs_for_solution

    args = launcher.parse_args(
        ["--matrix", "graded-spd", "--n", str(n), "--tile", str(tile),
         "--ratio", "0D:100S", "--escalation", "tile"])
    t0 = time.perf_counter()
    rep, ok = launcher.run(args)
    wall = time.perf_counter() - t0
    log(f"solve n={n} tile={tile}: converged={rep.converged} "
        f"sweeps={rep.sweeps} final_map={rep.final_ratio} "
        f"fresh_resolutions={rep.fresh_resolutions} "
        f"storage={rep.storage_bytes}/{rep.uniform_high_bytes} B "
        f"wall_s={wall:.1f}")
    check(ok and rep.fresh_resolutions == 0
          and rep.storage_bytes < rep.uniform_high_bytes,
          "solve did not pass the launcher's gate (converged, zero fresh "
          "resolutions, storage saving)")

    # second reading, on the host in fp64 from the same seeds: the metric
    # recomputed from the returned x, and its forward error against the
    # bound the residual implies, ||x - x_true|| <= ||A^-1||·||Ax - b||
    # (the tolerance admits a large forward error on an ill-conditioned
    # operator, so a bound from this run's residual is what x must meet)
    a = graded_spd(n, cond=args.cond, rho=args.rho, seed=args.seed)
    x_true, b = rhs_for_solution(a, nrhs=args.nrhs, seed=args.seed + 1)
    x = np.asarray(rep.x, np.float64)
    metric = hpl_mxp_metric(a, x, b, DEFAULT_FORMATS)
    inv_norm = np.abs(np.linalg.inv(a)).sum(axis=1).max()
    kappa = np.abs(a).sum(axis=1).max() * inv_norm
    x_norm = np.abs(x_true).max()
    fwd = np.abs(x - x_true).max() / x_norm
    bound = inv_norm * np.abs(a @ x - b).max() / x_norm
    log(f"solve host check: metric={metric:.6g} (solver {rep.metric:.6g}, "
        f"tol {args.tol}) kappa_inf={kappa:.6g} forward_err={fwd:.6g} "
        f"residual_bound={bound:.6g}")
    check(np.isfinite(x).all() and metric <= args.tol
          and np.isclose(metric, rep.metric, rtol=1e-6),
          "the solver's metric does not hold for the x it returned")
    check(fwd <= bound * (1 + 1e-6),
          "forward error above the bound the residual implies")


# ---------------------------------------------------------------------------
# --four-chips: SUMMA on a 2x2 grid vs single-device mp_matmul
# ---------------------------------------------------------------------------

def phase_summa(n: int = 8192, tile: int = 128) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import MPMatrix, schedule
    from repro.core.accuracy import (check_against_fp64, class_error_bounds,
                                     error_scale)
    from repro.core.formats import DEFAULT_FORMATS as fset
    from repro.core.precision import Policy
    from repro.launch.mesh import make_grid_mesh
    from repro.tune import dispatch as TD
    from repro.tune.costmodel import GemmPlan

    P = Q = 2
    mesh = make_grid_mesh(P, Q)
    nt = n // tile
    pol = Policy(kind="ratio", ratio_high=0.5)
    pa = schedule.sorted_balanced_map(nt, nt, pol, axis=0, groups=P,
                                      fset=fset)
    pb = schedule.sorted_balanced_map(nt, nt, pol, axis=1, groups=Q,
                                      fset=fset)
    pc = schedule.balanced_ratio_map(nt, nt, pol, P, Q, fset=fset)
    ka, kb = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(ka, (n, n), jnp.float32)
    b = jax.random.normal(kb, (n, n), jnp.float32)
    A = MPMatrix.from_dense(a, pa, tile, fset)
    B = MPMatrix.from_dense(b, pb, tile, fset)
    C = MPMatrix.from_dense(jnp.zeros((n, n), jnp.float32), pc, tile, fset)
    a64, b64 = np.asarray(a, np.float64), np.asarray(b, np.float64)

    t0 = time.perf_counter()
    single = jax.block_until_ready(TD.mp_matmul(A, B, C))
    log(f"summa single-device mp_matmul {n}^3: "
        f"first_call_s={time.perf_counter() - t0:.2f}")
    s64 = np.asarray(single.to_dense(), np.float64)
    rep = check_against_fp64(s64, a64, b64, None, pa, pb, pc, tile, fset)
    check(rep["ok"], f"single-device mp_matmul outside its bound: {rep}")
    # agreement: within twice the registry bound (each side carries its
    # own rounding budget)
    bounds = class_error_bounds(pa, pb, pc, n, fset)
    scale = error_scale(a64, b64)
    sel = np.repeat(np.repeat(pc, tile, 0), tile, 1)
    for local in ("ref", "grouped"):
        plan = GemmPlan(path=local, bm=tile, bn=tile, bk=tile)
        t0 = time.perf_counter()
        out = jax.block_until_ready(
            TD.summa_mp_matmul(A, B, C, mesh=mesh, plan=plan))
        dt = time.perf_counter() - t0
        devices = {s.device for buf in out.bufs
                   for s in buf.addressable_shards}
        o64 = np.asarray(out.to_dense(), np.float64)
        rep = check_against_fp64(o64, a64, b64, None, pa, pb, pc, tile,
                                 fset)
        err = np.abs(o64 - s64)
        agree = {fset.names[c]: float((err[sel == c]
                                       / (2 * bd * scale[sel == c])).max())
                 for c, bd in bounds.items() if (sel == c).any()}
        worst = {fset.names[c]: v for c, v in rep["worst_ratio"].items()}
        log(f"summa 2x2 local={local}: ok={rep['ok']} "
            f"worst_ratio={worst} vs_single={agree} "
            f"devices={len(devices)} first_call_s={dt:.2f}")
        check(rep["ok"], f"SUMMA {local} outside its bound")
        check(all(v <= 1.0 for v in agree.values()),
              f"SUMMA {local} disagrees with single-device mp_matmul")
        check(len(devices) == 4,
              f"SUMMA output on {len(devices)} devices, not 4")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 SUMMA GEMM and its one-device "
                         "comparison (needs four chips)")
    args = ap.parse_args(argv)
    try:
        dev = preflight()
        import jax
        phases = ([("summa", phase_summa)] if args.four_chips else
                  [("gemm", phase_gemm), ("serve", phase_serve),
                   ("solve", phase_solve)])
        if args.four_chips:
            check(len(jax.devices()) >= 4,
                  f"--four-chips needs 4 devices, have {len(jax.devices())}")
        for name, phase in phases:
            t0 = time.perf_counter()
            log(f"=== phase {name}")
            phase()
            log(f"=== phase {name} passed in "
                f"{time.perf_counter() - t0:.1f}s")
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
