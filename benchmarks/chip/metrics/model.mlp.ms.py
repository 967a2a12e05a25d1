"""Device time per step, in ms, of the ops under the program's
``model.mlp`` scope: each block's FFN (or MoE) pre-norm, FFN and
residual add, forward, backward and recomputation."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "model.mlp")
