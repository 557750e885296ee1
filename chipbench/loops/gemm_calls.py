"""Traffic loop ``gemm_calls``: back-to-back calls of the GEMM the driver
set up, each waited for, until the window's seconds have passed."""
from __future__ import annotations

from chipbench import counts


def _dispatch_counts() -> dict:
    """The program's ``dispatch.calls`` counter, by execution path."""
    from repro import obs
    out = {}
    for labels, counter in obs.metrics_registry().series("dispatch.calls"):
        out[labels["path"]] = out.get(labels["path"], 0) + int(counter.value)
    return out


def window(state: dict, seconds: float, span, clock) -> dict:
    import jax

    A, B, C, call = state["A"], state["B"], state["C"], state["call"]
    before = _dispatch_counts()
    calls = 0
    out = None
    t0 = clock()
    while clock() - t0 < seconds:
        with span("gemm.call"):
            out = None      # free the last output before the next call
            out = jax.block_until_ready(call(A, B, C))
        calls += 1
    t1 = clock()
    state["out"] = out
    paths = {k: v - before.get(k, 0) for k, v in _dispatch_counts().items()
             if v - before.get(k, 0)}
    notes = [f"calls={calls} dispatch paths in the window: {paths}"]
    maps, kind = state["maps"], jax.devices()[0].device_kind
    if kind in counts.peak_table():
        least = counts.gemm_least_time(maps["A"], maps["B"], maps["C"],
                                       state["tile"], kind)
        notes.append(f"least time a call {least['least_s']:.6f} s, bound "
                     f"by {least['bound']} (flops {least['flop_s']:.6f} s, "
                     f"bytes {least['byte_s']:.6f} s)")
    return {"t0": t0, "t1": t1, "calls": calls, "attempted": calls,
            "failed": 0, "maps": maps, "notes": notes}
