"""Device time per step, in ms, of the fused momentum-SGD kernel
(kernels/fused_sgd), averaged over the chips."""
KERNEL = "fused_sgd"


def read(ctx):
    names = ctx["kernels"](KERNEL)
    if ctx["trace"] is None or not names or not ctx["steps"]:
        return None
    seconds = ctx["lib"].op_seconds(ctx["trace"], names.__contains__)
    return seconds / ctx["steps"] * 1e3 if seconds > 0 else None
