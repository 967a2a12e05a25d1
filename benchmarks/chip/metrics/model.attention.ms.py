"""Device time per step, in ms, of the ops under the program's
``model.attention`` scope: each block's attention pre-norm, attention
and residual add, forward, backward and recomputation."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "model.attention")
