"""Device time per step, in ms, of the ops under the program's
``model.embed`` scope: the embedding gather, and in backward its
scatter-add into the table (``scopes.py``: a fusion takes its root's
scope; an op without one inherits its neighbours')."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "model.embed")
