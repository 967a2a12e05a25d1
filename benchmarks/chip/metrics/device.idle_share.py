"""The share of the traced window, in %, in which no operation ran on the
device (averaged over the chips)."""


def read(ctx):
    if ctx["trace"] is None or not ctx["window_s"]:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
