#!/usr/bin/env python3
"""Readings the correctness limits are set from: for each seed, the numbers
the program's run gives and the numbers its control gives (the reference
computed one format lower, standing in for the program), at the cell's own
size and load, all in one process.

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,3 \
        --seconds <s> [--out <file.jsonl>]

Each seed sets the cell up, runs a window of ``--seconds`` (long enough to
finish the traffic's longest requests), collects its outputs and prints
one JSON line ``{"seed", "program", "control"}``.  The benchmark's own
runs never run the control.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from chipbench import run as harness  # noqa: E402


def readings(workload: str, seed: int, seconds: float) -> dict:
    spec = harness.cell_spec(workload, seed)
    driver = harness.load_module("drivers", spec["config"]["driver"])
    loop = harness.load_module("loops", spec["traffic"]["loop"])
    t = time.perf_counter()
    state = driver.setup(spec)
    record = loop.window(state, seconds, lambda _: contextlib.nullcontext(),
                         time.perf_counter)
    payload = driver.collect(spec, state, record)
    del state
    gc.collect()
    program = driver.numbers(spec, payload)
    control = driver.numbers(spec, payload, control=True)
    return {"seed": seed, "program": program, "control": control,
            "attempted": record["attempted"], "failed": record["failed"],
            "seconds": time.perf_counter() - t}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    try:
        jax = harness.prepare_jax(args.workload)
        spec = harness.cell_spec(args.workload, 0)
        harness.check_device(jax, spec["cell"]["chips"])
    except harness.Refused as e:
        harness.log(f"refused: {e}")
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        line = json.dumps(readings(args.workload, seed, args.seconds))
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
