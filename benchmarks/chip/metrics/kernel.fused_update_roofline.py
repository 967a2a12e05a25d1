"""The fused momentum-SGD kernel's share of its roofline, in %: the least
time the chip could take for its bytes (or its operations, if those
bound it), over the kernel's measured device time. Bytes and operations
come from the kernel's operands and results in the compiled program
(costs/fused_sgd.py)."""
KERNEL = "fused_sgd"


def read(ctx):
    names = ctx["kernels"](KERNEL)
    if ctx["trace"] is None or not names or not ctx["steps"]:
        return None
    seconds = ctx["lib"].op_seconds(ctx["trace"], names.__contains__)
    if seconds <= 0:
        return None
    cost = ctx["costs"](KERNEL).cost
    flops = nbytes = 0.0
    for call in ctx["calls"]:
        if call["name"] in names:
            f, b = cost(call, ctx["lib"].shapes_bytes)
            flops, nbytes = flops + f, nbytes + b
    peaks = ctx["peaks"]
    least = max(nbytes / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops"])
    return 100.0 * least * ctx["steps"] / seconds
