"""Device time per step, in ms, of the ops under the program's
``model.layers`` scope: the whole layer scan, forward, backward and
recomputation, with its parameter slicing and gradient stacking; it
holds ``model.attention`` and ``model.mlp``, whose ops count here also
where XLA hoists them out of the scan."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "model.layers", "model.attention",
                           "model.mlp")
