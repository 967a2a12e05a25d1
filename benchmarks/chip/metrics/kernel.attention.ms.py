"""Device time per step, in ms, of the causal flash-attention kernels
(kernels/flash_attention: the forward, its recomputation in the layer
scan's backward, dK/dV and dQ), averaged over the chips. None where no
attention call took the kernel."""
KERNEL = "flash_attention"


def read(ctx):
    names = ctx["kernels"](KERNEL)
    if ctx["trace"] is None or not names or not ctx["steps"]:
        return None
    seconds = ctx["lib"].op_seconds(ctx["trace"], names.__contains__)
    return seconds / ctx["steps"] * 1e3 if seconds > 0 else None
