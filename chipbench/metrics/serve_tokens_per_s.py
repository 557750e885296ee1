"""Generated tokens of every request completed in the window, over the
window's wall time (host clock; the window ends with its last round)."""


def read(ctx):
    rec = ctx["record"]
    return rec["generated"] / (rec["t1"] - rec["t0"])
