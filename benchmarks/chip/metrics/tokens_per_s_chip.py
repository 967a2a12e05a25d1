"""Tokens trained per second per chip: every token of every step in the
window, over the window's seconds (its start to the last step's
completion stamp), over the chips."""


def read(ctx):
    run = ctx["run"]
    stamps = run["stamps"]
    if not stamps:
        return None
    seconds = stamps[-1] - run["t_start"]
    return len(stamps) * ctx["tokens_per_step"] / seconds / ctx["chips"]
