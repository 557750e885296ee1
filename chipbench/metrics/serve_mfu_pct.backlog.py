"""The whole serving step's share of the chip's bf16 peak: model FLOPs of
the prompt and generated tokens of every request completed in the traced
window (``counts.transformer_flops``: each prompt position and each
generated token fed back, with attention over its context), over the
traced window times the peak."""
from chipbench import counts


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    model = ctx["spec"]["config"]["model"]
    flops = sum(counts.transformer_flops(model, p + n - 1)
                for p, n in ctx["record"]["requests"])
    peak = counts.peaks(ctx["device_kind"])["bf16_flops"]
    return 100.0 * flops / (tr["window_s"] * peak)
