"""The 90th percentile of the durations of all steps in the window, in
ms. A step's duration is the gap between its completion stamp and the
previous one (the window's start, for the first)."""
import statistics


def read(ctx):
    run = ctx["run"]
    edges = [run["t_start"]] + run["stamps"]
    steps = [b - a for a, b in zip(edges, edges[1:])]
    if len(steps) < 10:
        return None
    return statistics.quantiles(steps, n=10, method="inclusive")[8] * 1e3
