"""Share of the chip's peak that the search's calibration forwards reach:
the forward's model operations per proposal (matmuls, and attention over the
causal half), times the proposals in the traced window, over the window and
the bf16 peak."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not ctx.get("proposals"):
        return None
    flops = ctx["flops_per_proposal"] * ctx["proposals"]
    return 100.0 * flops / tr["window_s"] / ctx["peaks"]["bf16_flops_per_s"]
