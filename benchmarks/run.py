"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and (with ``--out``) persists the
rows in the bench-schema JSON (``bench_io``) the CI perf-trajectory lane
uploads.  Every sub-benchmark runs even if an earlier one raises: errors
are collected, a summary table is printed, and only then does the harness
exit nonzero (the previous behaviour — die on the first exception with the
remaining benchmarks silently skipped — is the bug this replaces).

``--smoke`` shrinks every benchmark to tiny interpret-mode shapes (CI: the
point is the *trajectory* of the numbers, not their absolute scale).

Figure scripts that need many host devices (fig4 weak scaling; the dry-run
itself) run as subprocesses on the CPU backend, so this process keeps the
default single device and the only claim on an accelerator.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time
import traceback

# make `python benchmarks/run.py` work from anywhere: the repo root (for
# the `benchmarks` package) and src/ (for `repro`) go on sys.path
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _subprocess_rows(module: str, timeout: int = 1800) -> tuple[list, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    # the child works on forced host devices; this process has touched JAX
    # and holds the accelerator, which a second process must never claim
    env["JAX_PLATFORMS"] = "cpu"
    t0 = time.perf_counter()
    r = subprocess.run([sys.executable, "-m", module], env=env,
                       capture_output=True, text=True, timeout=timeout)
    dt = (time.perf_counter() - t0) * 1e6
    if r.returncode != 0:
        sys.stderr.write(r.stdout[-2000:] + r.stderr[-2000:])
        raise RuntimeError(f"{module} exited {r.returncode}")
    return [(module, dt, "ok")], r.stdout


def _bench_fig2(smoke: bool) -> list[tuple]:
    from benchmarks import fig2_precision_map
    return fig2_precision_map.bench(smoke=smoke)


def _bench_fig3(smoke: bool) -> list[tuple]:
    from benchmarks import fig3_shared_memory
    return fig3_shared_memory.bench(smoke=smoke)


def _bench_fig4(smoke: bool) -> list[tuple]:
    # fig4 weak scaling (subprocess: needs 256 host devices); skipped in
    # smoke mode — the forced-device jax bring-up dwarfs the tiny shapes
    if smoke:
        return [("fig4_scaling", 0.0, "skipped:smoke")]
    rows, out = _subprocess_rows("benchmarks.fig4_scaling")
    ratio = "?"
    for line in out.splitlines():
        if line.startswith("ratio "):
            ratio = line.split()[1]
        parts = line.split()
        if (len(parts) >= 9 and parts[0][0].isdigit() and "x" in parts[0]
                and parts[6].endswith("%")):
            rows.append((f"fig4_{ratio.replace(':', '_')}_grid_{parts[0]}",
                         0.0, f"chips={parts[1]};eff_ovl={parts[6]};"
                         f"eff_seq={parts[7]}"))
    return rows


def _bench_kernel_micro(smoke: bool) -> list[tuple]:
    # kernel micro (interpret mode — semantic cost only, not TPU timing)
    import jax
    import jax.numpy as jnp
    from repro.core import MPMatrix, make_map
    from repro.core.precision import Policy
    from repro.kernels import ops
    n, t = (32, 16) if smoke else (64, 16)
    a = jax.random.normal(jax.random.PRNGKey(0), (n, n))
    pol = Policy(kind="ratio", ratio_high=0.5)
    A = MPMatrix.from_dense(a, make_map((n, n), t, pol), t)
    C = MPMatrix.from_dense(jnp.zeros((n, n)), make_map((n, n), t, pol), t)
    t0 = time.perf_counter()
    ops.mp_gemm(A, A, C)
    return [(f"kernel_mp_gemm_tile_interp_{n}",
             (time.perf_counter() - t0) * 1e6, "interpret-mode")]


def _bench_obs_overhead(smoke: bool) -> list[tuple]:
    # zero-cost-when-disabled audit: the per-dispatch obs cost (one
    # labeled registry counter inc + one null span) vs one warm dispatch
    # through tune.mp_matmul — the acceptance bar is <1% overhead
    import jax
    import jax.numpy as jnp
    from repro import obs
    from repro.core import MPMatrix, make_map
    from repro.core.precision import Policy
    from repro.obs.metrics import MetricsRegistry
    from repro.tune import dispatch as TD
    reg = MetricsRegistry()
    n_ops = 20_000 if smoke else 100_000
    t0 = time.perf_counter()
    for _ in range(n_ops):
        reg.counter("dispatch.calls", path="grouped", op="nn").inc()
        with obs.span("gemm.dispatch", "gemm"):
            pass
    per_us = (time.perf_counter() - t0) / n_ops * 1e6
    n, t = 32, 16
    a = jax.random.normal(jax.random.PRNGKey(0), (n, n))
    pa = make_map((n, n), t, Policy(kind="ratio", ratio_high=0.5))
    A = MPMatrix.from_dense(a, pa, t)
    C = MPMatrix.from_dense(jnp.zeros((n, n)), pa, t)
    TD.mp_matmul(A, A, C)               # warm the dispatch path
    t0 = time.perf_counter()
    TD.mp_matmul(A, A, C)
    disp_us = (time.perf_counter() - t0) * 1e6
    pct = 100.0 * per_us / max(disp_us, 1e-9)
    return [("obs_disabled_overhead", per_us,
             f"dispatch_us={disp_us:.0f};overhead={pct:.4f}%")]


def _bench_tune_table(smoke: bool) -> list[tuple]:
    # tune table: cost-model vs measured plan ranking + cache-routed
    # dispatch vs reference (the autotuner acceptance gate)
    from benchmarks import tune_table
    return tune_table.bench(smoke=smoke)


def _bench_roofline(smoke: bool) -> list[tuple]:
    # roofline table summary (from cached dry-run artifacts, if present)
    from benchmarks import roofline
    rows = []
    try:
        cells = roofline.load_cells("results/dryrun")
    except Exception as e:  # dry-run not yet executed
        return [("roofline_table", 0.0, f"unavailable:{e}")]
    for c in cells:
        r = roofline.roofline_terms(c)
        if r["mesh"] != "16x16":
            continue
        rows.append((f"roofline_{r['arch']}_{r['shape']}",
                     r["step_s_lower_bound"] * 1e6,
                     f"dom={r['dominant']};roofl="
                     f"{100 * r['roofline_fraction']:.0f}%"))
    return rows or [("roofline_table", 0.0, "unavailable:no 16x16 cells")]


BENCHES = [
    ("fig2_precision_map", _bench_fig2),
    ("fig3_shared_memory", _bench_fig3),
    ("fig4_scaling", _bench_fig4),
    ("kernel_micro", _bench_kernel_micro),
    ("obs_overhead", _bench_obs_overhead),
    ("tune_table", _bench_tune_table),
    ("roofline", _bench_roofline),
]


def run_benches(benches, smoke: bool = False
                ) -> tuple[list[tuple], list[dict]]:
    """Run every (name, fn) bench; never stop at a failure.  Returns
    (rows, errors) where each error records the bench name and the
    exception (rows additionally carry a FAILED marker row)."""
    rows: list[tuple] = []
    errors: list[dict] = []
    for name, fn in benches:
        try:
            rows += fn(smoke)
        except Exception as e:
            traceback.print_exc()
            errors.append({"name": name, "error": f"{type(e).__name__}: {e}"})
            rows.append((name, 0.0, f"FAILED:{type(e).__name__}"))
    return rows, errors


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny interpret-mode shapes (CI perf trajectory)")
    ap.add_argument("--out", default="",
                    help="write rows to this bench-schema JSON path")
    args = ap.parse_args(argv)

    rows, errors = run_benches(BENCHES, smoke=args.smoke)

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    if args.out:
        from benchmarks.bench_io import write_bench
        write_bench(args.out, "gemm", rows,
                    meta={"smoke": args.smoke}, errors=errors)
        print(f"wrote {args.out}")

    if errors:
        print(f"\n{len(errors)} benchmark(s) FAILED:", file=sys.stderr)
        for e in errors:
            print(f"  {e['name']:24s} {e['error']}", file=sys.stderr)
        return 1
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
