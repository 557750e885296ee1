"""Split the device's idle time of a traced window by the host's spans:
the harness's own (``chipbench.*``) and the program's (``repro.*``, the
profiler annotations of ``repro.obs`` spans).

``trace.reduce`` gives the window, busy time and device operations and
cuts idle time at the harness's spans only; this module reads the same
``.xplane.pb`` and also takes the program's spans, so an idle gap inside
``gemm.call`` or ``serve.run`` is named by the phase of the program the
host was in.  Per device, the idle pieces of the window (its complement
of the busy union) are swept once against the sorted span edges:

* ``idle_gaps``: each idle piece goes to the innermost (shortest) span of
  either kind that covers it, summed per span name; harness spans are
  named without their ``chipbench.`` prefix, program spans keep ``repro.``;
* ``idle_by_span``: for each span name, the idle time covered by at least
  one span of that name (inclusive: a span and everything inside it);
* ``host_spans``: for each span name, how many spans the window holds and
  their summed host time.

All times are seconds, averaged over the devices as ``trace.reduce``
averages busy time.  Run it on a traced run's directory to print them::

    python3 chipbench/spans.py .chipbench/trace/<workload>
"""
from __future__ import annotations

import heapq
import os
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import trace  # noqa: E402

PROGRAM = "repro."
OUTSIDE = "outside harness spans"
#: where ``run.py`` writes a traced run's profile, by workload
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".chipbench", "trace")


def load(path: str, device_ids) -> dict:
    """Host spans of both kinds and device op intervals of the trace at
    ``path`` (the shape ``trace.reduce_events`` takes)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    wanted = {f"/device:TPU:{i}" for i in device_ids}
    spans, devices = [], {}
    for plane in pd.planes:
        if plane.name in wanted:
            devices[plane.name] = trace._device_ops(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((trace.PREFIX, PROGRAM)):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    missing = wanted - set(devices)
    if missing:
        raise ValueError(f"no device plane for {sorted(missing)} in {path}")
    return {"spans": spans, "devices": devices}


def _label(name: str) -> str:
    return name[len(trace.PREFIX):] if name.startswith(trace.PREFIX) \
        else name


def _idle(dev_ops, lo: float, hi: float) -> list:
    """The window's stretches in which the device ran nothing, in order."""
    edges = [lo] + [x for iv in trace.union(dev_ops, lo, hi) for x in iv] \
        + [hi]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def _sweep(idle: list, spans: list, gaps: dict, inclusive: dict) -> None:
    """Give each idle piece to the innermost span over it (``gaps``) and to
    every span name over it (``inclusive``).  ``spans`` are
    ``(start, end, label)`` without the window; among spans of equal
    length the first listed is the innermost."""
    edges = sorted([(s, 0, i) for i, (s, _, _) in enumerate(spans)]
                   + [(e, 1, i) for i, (_, e, _) in enumerate(spans)])
    open_spans: list = []           # heap of (length, index, end, label)
    depth: dict = {}                # label -> spans of it now open
    k = 0
    for a, b in idle:
        t = a
        while t < b:
            while k < len(edges) and edges[k][0] <= t:
                _, leaving, i = edges[k]
                s, e, label = spans[i]
                if not leaving:
                    heapq.heappush(open_spans, (e - s, i, e, label))
                    depth[label] = depth.get(label, 0) + 1
                elif depth[label] == 1:
                    del depth[label]
                else:
                    depth[label] -= 1
                k += 1
            while open_spans and open_spans[0][2] <= t:
                heapq.heappop(open_spans)
            nxt = min(b, edges[k][0]) if k < len(edges) else b
            who = open_spans[0][3] if open_spans else OUTSIDE
            gaps[who] = gaps.get(who, 0.0) + (nxt - t)
            for label in depth:
                inclusive[label] = inclusive.get(label, 0.0) + (nxt - t)
            t = nxt


def reduce_events(ev: dict) -> dict:
    windows = [(s, e) for s, e, n in ev["spans"] if n == trace.WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {trace.WINDOW} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    spans = [(s, e, _label(n)) for s, e, n in ev["spans"]
             if n != trace.WINDOW]
    gaps, inclusive, host = {}, {}, {}
    for dev_ops in ev["devices"].values():
        _sweep(_idle(dev_ops, lo, hi), spans, gaps, inclusive)
    for s, e, label in spans:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            n, total = host.get(label, (0, 0.0))
            host[label] = (n + 1, total + d)
    n = len(ev["devices"])
    return {
        "idle_gaps": [[k, v / n / 1e9] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])],
        "idle_by_span": {k: v / n / 1e9 for k, v in inclusive.items()},
        "host_spans": {k: [c, t / 1e9] for k, (c, t) in
                       sorted(host.items(), key=lambda kv: -kv[1][1])},
    }


def reduce(trace_dir: str, device_ids) -> dict:
    return reduce_events(load(trace.find(trace_dir), device_ids))


def idle_pct(ctx: dict, names) -> float | None:
    """100 × the idle time under any of the program spans ``names``
    (inclusive), over the traced window; ``None`` when the trace holds
    none of them (a program that does not annotate those phases)."""
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    if "spans" not in ctx:
        import jax
        used = jax.devices()[: ctx["spec"]["cell"]["chips"]]
        ctx["spans"] = reduce(
            os.path.join(TRACE_ROOT, ctx["spec"]["workload"]),
            [d.id for d in used])
    red = ctx["spans"]
    if not any(n in red["host_spans"] for n in names):
        return None
    return (100.0 * sum(red["idle_by_span"].get(n, 0.0) for n in names)
            / tr["window_s"])


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--devices", default="0",
                    help="comma-separated device ids (default 0)")
    args = ap.parse_args(argv)
    ids = [int(x) for x in args.devices.split(",")]
    print(json.dumps(reduce(args.trace_dir, ids)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
