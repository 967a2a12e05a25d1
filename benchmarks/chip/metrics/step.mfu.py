"""The whole step's share of the chip's bf16 peak, in %: model
operations per token (``costs/<family>.py`` for the family whose
reference the configuration names, recomputation not counted) times the
tokens of the traced steps, over the traced window's seconds, the chips
and the peak."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not ctx["steps"]:
        return None
    model = ctx["cell"].config["model"]
    per_token = ctx["costs"](ctx["cell"].config["reference"]).flops_per_token(
        model, ctx["cell"].traffic["seq_len"])
    flops = per_token * ctx["tokens_per_step"] * ctx["steps"]
    peak = ctx["peaks"]["bf16_flops"] * ctx["chips"]
    return 100.0 * flops / ctx["window_s"] / peak
