"""Share of the roofline reached by the device work of the traced GEMM
calls: the problem's least time (``counts.gemm_least_time``) times the
calls, over the device-busy time of the traced window.  All device work
in a GEMM cell's window is the GEMM."""
from chipbench import counts


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["busy_s"] <= 0:
        return None
    maps = ctx["record"]["maps"]
    least = counts.gemm_least_time(maps["A"], maps["B"], maps["C"],
                                   ctx["spec"]["config"]["tile"],
                                   ctx["device_kind"])
    return 100.0 * least["least_s"] * ctx["record"]["calls"] / tr["busy_s"]
