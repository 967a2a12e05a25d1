"""Device time per step, in ms, of the ops under the program's
``sync.pack`` and ``sync.unpack`` scopes: gradients and parameters into
the flat buffers, and the updated buffer back to the parameter leaves."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "sync.pack", "sync.unpack")
