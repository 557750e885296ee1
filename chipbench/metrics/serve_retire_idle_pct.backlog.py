"""Share of the traced window in which the device idles while the engine
runs a retirement pass (``repro.serve.retire_pass``: the device-to-host
drain of sampled tokens, retiring rows, and the refill prefill of freed
slots): the idle time under that span, inclusive, over the window."""
from chipbench import spans


def read(ctx):
    return spans.idle_pct(ctx, ("repro.serve.retire_pass",))
