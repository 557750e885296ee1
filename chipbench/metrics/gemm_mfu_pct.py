"""The whole call's share of the chip's peak: the problem's least time
(``counts.gemm_least_time``) times the calls completed in the traced
window, over the window's wall time."""
from chipbench import counts


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    maps = ctx["record"]["maps"]
    least = counts.gemm_least_time(maps["A"], maps["B"], maps["C"],
                                   ctx["spec"]["config"]["tile"],
                                   ctx["device_kind"])
    return (100.0 * least["least_s"] * ctx["record"]["calls"]
            / tr["window_s"])
