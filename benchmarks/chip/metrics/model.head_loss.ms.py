"""Device time per step, in ms, of the ops under the program's
``model.head_loss`` scope: the final norm, the tied head and the
chunked cross-entropy, forward, backward and recomputation."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "model.head_loss")
