#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Everything a cell needs is found by name: its entry in ``BENCHMARK.json``
names a configuration (``configs/<config>.json``, whose ``driver`` names
``drivers/<driver>.py``) and a traffic mix (``traffic/<traffic>.json``,
whose ``loop`` names ``loops/<loop>.py``); its correctness limits are ``limits/<workload>.json``, and each metric is
read by ``metrics/<metric>.py``.  With ``--trace 0`` the run reports the
cell's end-to-end metrics; with ``--trace 1`` it traces the window with the
JAX profiler and reports the per-layer metrics.

The run refuses (exit code 2, no result) without a TPU, with fewer chips
than the cell asks for, or on a device kind missing from ``peaks.json``.
The last line of standard output is one JSON result; the numbers compared
for correctness are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: run-time state inside the checkout: compile cache, plan cache, traces
STATE = os.path.join(ROOT, ".chipbench")


class Refused(RuntimeError):
    """The run cannot be made here; no result is printed."""


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise Refused(f"no {kind[:-1]} file {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    if not os.path.exists(path):
        raise Refused(f"missing {path}")
    with open(path) as f:
        return json.load(f)


def cell_spec(workload: str, seed: int) -> dict:
    """The cell's configuration, traffic, limits and metrics, by name."""
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"unknown workload {workload!r}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {
        "workload": workload, "seed": seed, "cell": cell,
        "config": _json(os.path.join(ROOT, entry["file"])),
        "traffic": _json(os.path.join(HERE, "traffic",
                                      f"{cell['traffic']}.json")),
        "limits": _json(os.path.join(HERE, "limits", f"{workload}.json")),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def prepare_jax(workload: str):
    """Caches inside the checkout, then JAX (the device checks follow)."""
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise Refused(f"no src/repro in {ROOT}: run from a checkout of "
                      "the repository")
    os.makedirs(os.path.join(STATE, "jax"), exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(STATE, "jax")
    plans = os.path.join(STATE, f"plans-{workload}.json")
    if os.path.exists(plans):
        os.remove(plans)
    os.environ["REPRO_TUNE_CACHE"] = plans      # fresh for every run
    os.environ["REPRO_TUNE_CACHE_ONLY"] = "1"   # never measure
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def check_device(jax, chips: int):
    from chipbench import counts
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: the first device is {devs[0].platform}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    try:
        counts.peaks(devs[0].device_kind)
    except KeyError as e:
        raise Refused(str(e)) from None
    return devs


class CompileCounter:
    """Counts backend compilations and persistent-cache hits."""

    def __init__(self, jax):
        self.compiles = 0
        self.cache_hits = 0
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, int]:
        return self.compiles, self.cache_hits


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every number within its limit, and every limit read.  Work that was
    attempted and never finished is compared exactly (limit 0)."""
    limits = {**limits, "unfinished": 0}
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in numbers.items()}
    ok = (bool(numbers) and set(numbers) == set(limits)
          and all(v == v and v <= limits[k] for k, v in numbers.items()))
    return ok, checks


def read_metrics(defs: list, ctx: dict) -> dict:
    out = {}
    for m in defs:
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(args) -> dict:
    spec = cell_spec(args.workload, args.seed)
    jax = prepare_jax(args.workload)
    devs = check_device(jax, spec["cell"]["chips"])
    used = devs[: spec["cell"]["chips"]]
    counter = CompileCounter(jax)
    driver = load_module("drivers", spec["config"]["driver"])
    loop = load_module("loops", spec["traffic"]["loop"])
    trace_dir = os.path.join(STATE, "trace", args.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)

    def span(name):
        if not args.trace:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(f"chipbench.{name}")

    state = driver.setup(spec)
    setup_s = time.perf_counter() - T_START
    before = counter.snapshot()
    if args.trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with span("window"):
        record = loop.window(state, args.seconds, span, time.perf_counter)
    if args.trace:
        jax.profiler.stop_trace()
    after = counter.snapshot()
    log(f"setup_s={setup_s:.3f} compiles_in_window={after[0] - before[0]} "
        f"cache_hits_in_window={after[1] - before[1]} "
        f"compiles_in_setup={before[0]} cache_hits_in_setup={before[1]}")
    for line in record.get("notes", []):
        log(line)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    log(f"peak_bytes_in_use={peak}")
    payload = driver.collect(spec, state, record)
    del state
    gc.collect()
    numbers = {**driver.numbers(spec, payload),
               "unfinished": record["failed"]}
    correct, checks = judge(numbers, spec["limits"])

    ctx = {"spec": spec, "record": record, "setup_s": setup_s,
           "device_kind": devs[0].device_kind, "trace": None}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": record["attempted"],
              "failed": record["failed"]}
    if args.trace:
        from chipbench import trace
        red = trace.reduce(trace_dir, [d.id for d in used])
        ctx["trace"] = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["metrics"] = read_metrics(spec["per_layer"], ctx)
        result["breakdown"] = {"device_ops": red["device_ops"][:10],
                               "idle_gaps": red["idle_gaps"][:10]}
    else:
        result["metrics"] = read_metrics(spec["end_to_end"], ctx)
    result["device"] = device
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except Refused as e:
        log(f"refused: {e}")
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
