"""Padded prompt positions over real plus padded ones, in the window only:
the change of the engine's ``stats()["tokens"]`` counters across it."""


def read(ctx):
    rec = ctx["record"]
    b, a = rec["tokens_before"], rec["tokens_after"]
    real = a["prompt"] - b["prompt"]
    pad = a["padded"] - b["padded"]
    if real + pad <= 0:
        return None
    return 100.0 * pad / (real + pad)
