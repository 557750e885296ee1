"""Share of the traced window in which the device idles while the host
expands the output's tile map to one class per element
(``repro.gemm.expand_map``) or uploads it (``repro.gemm.upload_map``):
the idle time under either span, inclusive, over the window."""
from chipbench import spans


def read(ctx):
    return spans.idle_pct(ctx, ("repro.gemm.expand_map",
                                "repro.gemm.upload_map"))
