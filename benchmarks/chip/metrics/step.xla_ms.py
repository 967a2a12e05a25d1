"""Device time per step, in ms, in which an operation ran that is
neither a Pallas kernel nor a collective: forward, backward, loss and
the sync engine's packing and unpacking, until named scopes split them."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not ctx["steps"]:
        return None
    lib, kernels = ctx["lib"], ctx["kernel_ops"]
    other = lambda n: n in kernels or lib.is_collective(n)
    return lib.busy_s(trace, leave_out=other) / ctx["steps"] * 1e3
