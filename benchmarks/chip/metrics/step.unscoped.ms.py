"""The part of ``step.xla_ms``, in ms a step, that lies in none of the
program's ``model.*`` and ``sync.*`` scopes: XLA ops (no Pallas kernel,
no collective) that carry none, by their own name or by inheritance, and
the time in which an op that holds others (a loop) ran with none of
them. With it, the scoped readers sum to ``step.xla_ms``."""
from benchmarks.chip import scopes


def read(ctx):
    return scopes.unscoped_ms(ctx)
