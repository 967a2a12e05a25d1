"""Seconds from the process's start to the window's first step: loading,
weights, traffic, state, compilation (or the compile cache), the check's
first steps and the warm-up steps."""


def read(ctx):
    return ctx["run"]["setup_s"]
