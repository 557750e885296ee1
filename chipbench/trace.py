"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

* the traced window: the harness's ``chipbench.window`` span on the host;
* busy time: the union of the intervals in which an operation ran on each
  used device (its ``XLA Ops`` line), clipped to the window and averaged
  over the devices;
* device operations: summed time per innermost operation (an op that
  holds others, such as a ``while`` loop, is counted through them), named
  ``<program>/<operation>`` (the ``XLA Modules`` event that encloses it,
  and the HLO instruction's name), busiest first;
* idle gaps: every stretch of the window in which a device ran nothing,
  cut at the edges of the harness's spans (``chipbench.*``), each piece
  attributed to the innermost span that covers it, summed per span,
  longest first.
"""
from __future__ import annotations

import bisect
import glob
import os

WINDOW = "chipbench.window"
PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _module_of(modules: list, starts: list, t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < modules[i][1]:
        return modules[i][2]
    return "?"


def _device_ops(plane) -> list:
    """``(start, end, "<program>/<op>", innermost)`` of every op on a
    device plane."""
    lines = {line.name: line for line in plane.lines}
    modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                      e.name.split("(")[0])
                     for e in (lines[MODULES_LINE].events
                               if MODULES_LINE in lines else ()))
    starts = [m[0] for m in modules]
    ops = sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in (lines[OPS_LINE].events if OPS_LINE in lines
                            else ())), key=lambda o: (o[0], -o[1]))
    out, open_ops = [], []
    for s, e, name in ops:
        while open_ops and open_ops[-1][1] <= s:
            open_ops.pop()
        if open_ops:                      # nested: its parent is no leaf
            open_ops[-1][3] = False
        item = [s, e, name, True]
        open_ops.append(item)
        out.append(item)
    return [(s, e, f"{_module_of(modules, starts, s)}/"
                   f"{name.split(' = ')[0].lstrip('%')}", leaf)
            for s, e, name, leaf in out]


def load(path: str, device_ids) -> dict:
    """Host spans and device op intervals of the trace at ``path``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    wanted = {f"/device:TPU:{i}" for i in device_ids}
    spans, devices = [], {}
    for plane in pd.planes:
        if plane.name in wanted:
            devices[plane.name] = _device_ops(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    missing = wanted - set(devices)
    if missing:
        raise ValueError(f"no device plane for {sorted(missing)} in {path}")
    return {"spans": spans, "devices": devices}


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``[start, end)`` intervals clipped to ``[lo, hi)``."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _owner(spans, t: float) -> str:
    """Innermost harness span other than the window that covers ``t``."""
    best = None
    for s, e, name in spans:
        if name != WINDOW and s <= t < e and (best is None
                                              or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2][len(PREFIX):] if best else "outside harness spans"


def reduce_events(ev: dict) -> dict:
    windows = [(s, e) for s, e, n in ev["spans"] if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    lo, hi = windows[0]
    busy, ops, gaps = [], {}, {}
    for dev_ops in ev["devices"].values():
        merged = union(dev_ops, lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for s, e, name, leaf in dev_ops:
            d = min(e, hi) - max(s, lo)
            if leaf and d > 0:
                ops[name] = ops.get(name, 0.0) + d
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            cuts = sorted({s, e} | {x for a, b, _ in ev["spans"]
                                    for x in (a, b) if s < x < e})
            for a, b in zip(cuts, cuts[1:]):
                who = _owner(ev["spans"], (a + b) / 2)
                gaps[who] = gaps.get(who, 0.0) + (b - a)
    n = len(ev["devices"])

    def ranked(d):
        return [[k, v / n / 1e9]
                for k, v in sorted(d.items(), key=lambda kv: -kv[1])]

    return {"window_s": (hi - lo) / 1e9, "busy_s": sum(busy) / n / 1e9,
            "device_ops": ranked(ops), "idle_gaps": ranked(gaps)}


def reduce(trace_dir: str, device_ids) -> dict:
    return reduce_events(load(find(trace_dir), device_ids))
