"""Useful GEMM throughput: 2·m·n·k for every call completed in the window,
whatever path ran it, over the window's wall time (host clock)."""


def read(ctx):
    rec = ctx["record"]
    n = ctx["spec"]["config"]["n"]
    return 2.0 * n ** 3 * rec["calls"] / (rec["t1"] - rec["t0"]) / 1e12
